"""The benchmark's arrival model gives the cohorts the program's arrival
frontier draws from the same profiles and seed."""
import numpy as np
import pytest

from chipbench import arrivals, traffic

MIX = {"clusters": 4, "workers_per_cluster": 16,
       "arrivals": {"straggler_frac": 0.25, "straggler_slowdown": 4.0,
                    "base_speed": 1.0, "speed_spread": 0.2, "jitter": 0.3,
                    "failure_prob": 0.1}}


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_cohorts_match_the_program(seed):
    from repro.core.async_sim import AsyncScheduler, WorkerProfile
    profiles = traffic.arrival_profiles(MIX, seed)
    task_seed = traffic.derive(seed, traffic.TASK)
    sched = AsyncScheduler([WorkerProfile(*p) for p in profiles],
                           seed=task_seed, task_id="task-0", buffer_size=16)
    theirs = [sched.next_aggregation()[1] for _ in range(12)]
    mine = arrivals.cohorts(profiles, seed=task_seed, task_id="task-0",
                            buffer_size=16, events=12)
    assert all(np.array_equal(a, b) for a, b in zip(mine, theirs))
    assert all(m.sum() == 16 for m in mine)


def test_profiles_have_the_stated_stragglers():
    speeds = np.array([p[0] for p in traffic.arrival_profiles(MIX, 7)])
    assert np.sum(speeds > 2.0) == 16
    assert np.all((speeds > 0.79) & (speeds < 4.81))
