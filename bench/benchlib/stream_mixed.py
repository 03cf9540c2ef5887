"""The stream of a fleet that mixes MIG device generations.

The configuration's ``fleet.devices`` lists each device model with its
share of the hosts; the first is the reference device, whose profile
table the stream's Fig. 5 mix, per-VM CPU and RAM and per-profile counts
use (``stream.generate``'s ``fleet.profiles``).  The draws are
``stream.generate``'s, in its order, so one seed gives the same hosts,
arrivals, lifetimes and reference-device profiles as a one-model fleet
of that device.  On top of them:

  * each host's device model is drawn from a separate generator,
    ``default_rng([seed, 0xF1EE7])``, as the repository's
    ``workload/alibaba.py`` does, so the VM stream does not depend on the
    fleet mix;
  * each VM's GPU share ``u`` is mapped through Eqs. 27-30 onto every
    device's profile table: ``pid`` is (n, number of devices), column 0
    the reference device's.

Like ``stream``, this imports nothing of the program under test.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from .stream import iqr_filter, profile_u_hat

FLEET_STREAM = 0xF1EE7


def generate(config: dict, seed: int,
             n_vms: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Fleet and stream of a mixed-fleet ``config`` for ``seed``.

    Returns ``stream.generate``'s arrays, with ``pid`` of shape
    (n, devices), plus ``host_model`` (H,) and ``gpu_model`` (G,): each
    host's and each GPU's index into ``fleet.devices``."""
    fleet, st = config["fleet"], config["stream"]
    devices = fleet["devices"]
    ref = devices[0]
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

    names = [p["name"] for p in ref["profiles"]]
    pmix = st["profile_mix"]
    mix_names = list(pmix)
    w = np.array([pmix[k] for k in mix_names], np.float64)
    target = rng.choice(len(mix_names), size=n, p=w / w.sum())
    ref_u_hat = profile_u_hat(ref)
    base_u = np.array([ref_u_hat[names.index(k)] for k in mix_names])
    u = base_u[target] * np.exp(rng.normal(0.0, st["u_jitter_sigma"],
                                           size=n))
    u = np.clip(u, 1e-4, 1.0)
    pid = np.stack([
        np.argmin(np.abs(profile_u_hat(d)[None, :] - u[:, None]), axis=1)
        for d in devices], axis=1)

    sigma = st["duration_sigma"]
    mu = np.log(st["mean_duration_hours"]) - 0.5 * sigma ** 2
    duration = np.clip(rng.lognormal(mu, sigma, size=n),
                       st["min_duration_hours"], None)

    shares = np.array([d["share"] for d in devices], np.float64)
    host_model = np.random.default_rng([seed, FLEET_STREAM]).choice(
        len(devices), size=fleet["hosts"], p=shares / shares.sum())

    compute = np.array([p["compute"] for p in ref["profiles"]], float)
    size = np.array([p["size"] for p in ref["profiles"]], float)
    c0, c1 = st["vm_cpu"]
    r0, r1 = st["vm_ram"]
    cpu = c0 + c1 * compute[pid[:, 0]] / compute.max()
    ram = r0 + r1 * size[pid[:, 0]] / ref["num_blocks"]
    return dict(gpu_counts=gpu_counts.astype(np.int64), arrival=arrival,
                duration=duration, pid=pid.astype(np.int64), cpu=cpu,
                ram=ram, horizon=horizon,
                host_model=host_model.astype(np.int64),
                gpu_model=np.repeat(host_model, gpu_counts).astype(np.int64))


__all__ = ["generate", "FLEET_STREAM"]
