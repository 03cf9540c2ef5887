"""The one stream generator: an Alibaba cluster-trace-gpu-v2023 shaped
fleet and VM stream, drawn from a seed.

Every configuration file (``bench/configs/<name>.json``) holds the
parameters: the fleet's host GPU-count mix and MIG profile table, and the
stream's Fig. 5 profile mix, burst and lifetime shapes.  The maths is the
paper's §8.1 recipe, in the draw order the repository's
``workload/alibaba.py`` uses, so one seed gives the same stream there and
here:

  * bursty exponential inter-arrivals (a share stretched by a factor),
    cut by the IQR outlier filter, scaled so the stream spans its horizon;
  * each pod's GPU share drawn near a profile's Eq. 28-29 value by the
    Fig. 5 mix, then mapped back to the nearest profile (Eqs. 27-30);
  * lognormal lifetimes.

A stream longer than the configuration's ``vms`` keeps the same arrivals
per trace hour: its horizon grows with it.

The program under test gets only these arrays (through
``build_events_arrays`` and ``requests_from_trace``); the reference gets
them too, and nothing the program made.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def profile_u_hat(fleet: dict) -> np.ndarray:
    """Eqs. 28-29: each profile's compute x memory share, normalised."""
    B = fleet["num_blocks"]
    max_c = max(p["compute"] for p in fleet["profiles"])
    u = np.array([(p["compute"] / max_c) * (p["size"] / B)
                  for p in fleet["profiles"]])
    return u / u.max()


def iqr_filter(values: np.ndarray) -> np.ndarray:
    """§8.1: keep values within [Q1 - 1.5 IQR, Q3 + 1.5 IQR]."""
    q1, q3 = np.percentile(values, [25, 75])
    iqr = q3 - q1
    return values[(values >= q1 - 1.5 * iqr) & (values <= q3 + 1.5 * iqr)]


def generate(config: dict, seed: int,
             n_vms: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Fleet and stream of ``config`` for ``seed``.

    Returns host GPU counts and per-VM arrays in vm_id order:
    ``arrival`` and ``duration`` (hours, float64), ``pid`` (profile index),
    ``cpu`` and ``ram`` (float64), plus ``horizon`` (hours)."""
    fleet, st = config["fleet"], config["stream"]
    rng = np.random.default_rng(seed)
    n = int(n_vms or st["vms"])
    horizon = st["horizon_hours"] * n / st["vms"]

    mix = fleet["host_gpu_mix"]
    counts = np.array([int(k) for k in mix])
    probs = np.array([mix[k] for k in mix], np.float64)
    gpu_counts = rng.choice(counts, size=fleet["hosts"],
                            p=probs / probs.sum())

    n_raw = int(n * st["oversample"])
    inter = rng.exponential(horizon / n_raw, size=n_raw)
    burst = rng.random(n_raw) < st["burst_prob"]
    inter[burst] *= st["burst_factor"]
    inter = iqr_filter(inter)
    if inter.size < n:
        extra = rng.exponential(np.median(inter), size=n - inter.size)
        inter = np.concatenate([inter, extra])
    arrival = np.cumsum(inter[:n])
    arrival = arrival / arrival.max() * horizon

    names = [p["name"] for p in fleet["profiles"]]
    pmix = st["profile_mix"]
    mix_names = list(pmix)
    w = np.array([pmix[k] for k in mix_names], np.float64)
    target = rng.choice(len(mix_names), size=n, p=w / w.sum())
    u_hat = profile_u_hat(fleet)
    base_u = np.array([u_hat[names.index(k)] for k in mix_names])
    u = base_u[target] * np.exp(rng.normal(0.0, st["u_jitter_sigma"],
                                           size=n))
    u = np.clip(u, 1e-4, 1.0)
    pid = np.argmin(np.abs(u_hat[None, :] - u[:, None]), axis=1)

    sigma = st["duration_sigma"]
    mu = np.log(st["mean_duration_hours"]) - 0.5 * sigma ** 2
    duration = np.clip(rng.lognormal(mu, sigma, size=n),
                       st["min_duration_hours"], None)

    compute = np.array([p["compute"] for p in fleet["profiles"]], float)
    size = np.array([p["size"] for p in fleet["profiles"]], float)
    c0, c1 = st["vm_cpu"]
    r0, r1 = st["vm_ram"]
    cpu = c0 + c1 * compute[pid] / compute.max()
    ram = r0 + r1 * size[pid] / fleet["num_blocks"]
    return dict(gpu_counts=gpu_counts.astype(np.int64), arrival=arrival,
                duration=duration, pid=pid.astype(np.int64), cpu=cpu,
                ram=ram, horizon=horizon)


__all__ = ["generate", "profile_u_hat", "iqr_filter"]
