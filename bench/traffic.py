"""The one generator of the benchmark's inputs.

A traffic mix (``bench/traffic/<mix>.json``) and a deployment
(``bench/configs/<config>.json``) are data; this module turns them and a
seed into the inputs of one run. The draws follow the paper's default
composition draw for draw (arXiv:2204.13878, Sec. VII.B): one
``numpy.random.default_rng(seed)`` stream gives first the device of each
user (the catalog round-robin, then shuffled), then one ``(T, n)`` block
of uniforms for the Bernoulli app arrivals, then one ``(T, n)`` block of
app choices. Every seed gives the same sizes; only the draws differ.
"""
from __future__ import annotations

import numpy as np

N_DEVICES = 4     # Table II rows
N_APPS = 8        # Table II apps


def n_slots(horizon_s: float, t_d: float) -> int:
    return int(round(horizon_s / t_d))


def fleet_inputs(deployment: dict, traffic: dict, seed: int) -> dict:
    """``device`` (n,), ``app_sched`` (T, n) bool, ``app_choice`` (T, n)
    int64 for the deployment's ``n_users`` over its horizon."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    arrivals = traffic["arrivals"]
    if arrivals != "bernoulli":
        raise ValueError(f"unknown arrival process {arrivals!r}")
    if deployment["fleet"] != "table2_round_robin_shuffled":
        raise ValueError(f"unknown fleet {deployment['fleet']!r}")
    n = int(deployment["n_users"])
    T = n_slots(deployment["horizon_s"], deployment["t_d"])
    p = float(traffic["app_arrival_p"])
    rng = np.random.default_rng(seed)
    device = np.arange(n, dtype=np.int64) % N_DEVICES
    rng.shuffle(device)
    app_sched = rng.random((T, n)) < p
    app_choice = rng.integers(0, N_APPS, (T, n))
    return {"device": device, "app_sched": app_sched,
            "app_choice": app_choice}


def program_fleet(device):
    """The benchmark's device draw (rows of Table II), handed to the
    program as its fleet."""
    from repro.core.energy import DEVICE_NAMES, TESTBED, catalog_tables
    from repro.core.fleet import Fleet, FleetSpec

    class GivenFleet(Fleet):
        name = "bench_given"

        def build(self, rng, n_users):
            if n_users != len(device):
                raise ValueError(f"{len(device)} devices drawn for "
                                 f"{n_users} users")
            return FleetSpec(
                devices=tuple(TESTBED[DEVICE_NAMES[d]] for d in device),
                tables=catalog_tables(), device_ids=device.copy())

    return GivenFleet()
