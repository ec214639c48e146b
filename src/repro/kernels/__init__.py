"""Pallas kernel layer — the SDFL-B compute hot-spots.

Module map
----------
``pack``
    Flat-pack layer: a param pytree as ONE contiguous (W, D) matrix.
    ``PackSpec`` is the static slice metadata (leaf order, per-leaf
    offset/size/shape into the flat axis, pack dtype, total width D);
    rows are ``[leaf0.ravel() | leaf1.ravel() | ...]`` in
    ``jax.tree.leaves`` order. Dtype policy: the pack stores deltas in
    the tree's (uniform) param dtype — bf16 deltas carry full *relative*
    precision — and every kernel upcasts tiles to f32 on read. Trees
    mixing leaf dtypes are not packable and stay on the per-leaf path.

``tpu``
    What the kernels know about the chip: per-lowering dispatch
    (``on_tpu`` — kernel when lowered for a TPU, reference elsewhere) and
    tile planning against the scoped-VMEM budget (``plan_tiles``).

``trust_score``
    One-sweep trust statistics over the packed (W, D) update matrix:
    per-worker <u_w, c> / ‖u_w‖² plus ‖c‖² vs the consensus mean, in a
    single streamed HBM pass (full-W column strips; W-tiled, two sweeps,
    where a strip cannot fit VMEM).

``trust_agg``
    Trust-weighted aggregate Σ_w w_w·u_w → (D,) f32, one streamed pass
    (W tiles accumulated per D tile where a full-W strip cannot fit).

``fused_round``
    The fused device-resident trust round: chains ``trust_score`` +
    ``trust_agg`` over one packed matrix (2 streamed passes over the
    update volume — the information floor, since aggregation weights
    depend on global statistics of the whole matrix), plus the 2-D-grid
    async kernel folding pending buffers + participation masking into
    the same sweep. A round lowered for a TPU runs the Pallas kernels;
    lowered for CPU it runs the identical flat-jnp reference math
    (``SDFLB_FUSED_INTERPRET=1`` forces interpret-mode Pallas — the CI
    kernel-correctness smoke). Also the analytic HBM accounting
    (``streamed_bytes`` / ``update_passes``) behind the benchmark gate.

``ref``
    Exact jnp references for every kernel (the property-test oracles).

``ops``
    Jit'd public wrappers — what ``core``/``models`` import. Engagement:
    ``core.fl_step`` routes steps 3–5 of the round through this package
    when ``FederationConfig.fused_trust_path`` allows (auto-on for
    unsharded flat/CNN trees with one leaf dtype; per-leaf jnp reference
    otherwise).

``attention``
    Causal self-attention for MLA's training path: the bundled splash
    (flash) kernel over causal blocks only, v at its own head size, with
    its dq and dk/dv backward kernels. ``models.layers.apply_mla`` takes it
    where the program is lowered for a TPU, is not partitioned over
    several devices and S tiles into blocks; ``blocked_attention``
    elsewhere.

``swa_decode`` / ``ssd_scan``
    LLM-zoo hot loops (sliding-window decode attention; Mamba2/mLSTM
    SSD chunk scan) — unrelated to the trust round.
"""
