"""Plain sequential GRMU: the reference every cell's answers are held to.

A straightforward reading of the paper's Algorithms 1-5 on one device
model, one VM at a time, with no batching, no padding and no device.  It
imports nothing of the program under test and takes only the generated
stream (``benchlib.stream``) and the configuration file.

The discrete-time loop is the paper's Cloudy-style engine (§8): in each
1 h step, departures due before the step's end release their blocks, the
step's arrivals are offered in (arrival, vm_id) order, then the step-end
hook runs (defragmentation when a light request was rejected, then
consolidation when due), then the hourly sample is taken.

  * Alg. 1 (default block placement): a profile goes to the legal start
    that leaves the largest configuration capability (CC, Eq. 1); the
    first such start wins.
  * Algs. 2-3 (dual baskets): GPUs start in a pool in index order, with
    GPU 0 in the heavy basket and GPU 1 in the light one.  A full-GPU
    request looks in the heavy basket, any other in the light one; the
    first GPU (lowest index) that fits the profile and whose host has
    CPU and RAM headroom wins.  With none, the basket takes the lowest
    pool GPU while it holds fewer than its cap, and the request goes
    there if the host has headroom.
  * Alg. 4 (defragmentation): the light GPU with the highest
    fragmentation (first maximiser, positive, not empty) has its VMs
    replayed through Alg. 1 on an empty GPU in start-block order; VMs
    whose start changed count as intra-GPU migrations.  Nothing changes
    when a VM would not fit again or none would move.
  * Alg. 5 (consolidation): light GPUs that hold one half-GPU VM in one
    half are sources in index order; each moves to the first later such
    GPU that still takes it (profile fits, host headroom), and the
    emptied source returns to the pool.

Host CPU and RAM are counted in float32, as a fleet controller that keeps
32-bit counters would.

Each VM's placement history is kept with the moment each position began,
as a stamp ``2 * step + phase``: phase 0 while the step's departures and
arrivals are handled, phase 1 at its end (defragmentation,
consolidation).  ``arrival_step`` and ``release_step`` say in which step
an arrival is offered and a departure released.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

POOL, HEAVY, LIGHT = 0, 1, 2
EPS = 1e-9


def arrival_step(t: float, step_hours: float = 1.0) -> int:
    """The step whose arrivals include one at time ``t``."""
    return int(math.floor((t + EPS) / step_hours))


def release_step(t: float, arrived: int, step_hours: float = 1.0) -> int:
    """The step at whose start a VM leaving at ``t`` is released: the
    first whose end is at or past ``t``, and never its arrival's step
    (that step's departures are handled before its arrivals)."""
    return max(int(math.ceil((t + EPS) / step_hours)) - 1, arrived + 1)


class MigTables:
    """Alg. 1 and the fragmentation metric over every free-block mask of
    one device model (bit b set = block b free).  ``block_rule="first"``
    breaks Alg. 1 for the control run: each profile takes its first legal
    start instead of the one that keeps the most CC."""

    def __init__(self, fleet: dict, block_rule: str = "cc"):
        B = fleet["num_blocks"]
        profs = fleet["profiles"]
        self.num_blocks = B
        self.full = (1 << B) - 1
        self.sizes = [p["size"] for p in profs]
        self.heavy_profile = next(
            (i for i, p in enumerate(profs) if p["size"] == B), -1)
        self.half_profiles = {i for i, p in enumerate(profs)
                              if p["size"] == B // 2}
        self.lower_half = (1 << (B // 2)) - 1
        self.upper_half = self.full ^ self.lower_half
        slots = [[((1 << p["size"]) - 1) << s for s in p["starts"]]
                 for p in profs]
        starts = [list(p["starts"]) for p in profs]
        NM, NP = 1 << B, len(profs)

        def cc(free: int) -> int:
            return sum(1 for row in slots for m in row if m & free == m)

        self.fits = np.zeros((NM, NP), bool)
        self.start = np.full((NM, NP), -1, np.int64)
        self.after = np.zeros((NM, NP), np.int64)
        self.frag = np.zeros(NM, np.float32)
        for free in range(NM):
            for p in range(NP):
                best = -1
                for s, m in zip(starts[p], slots[p]):
                    if m & free == m:
                        # "first" is the control's broken Alg. 1: the
                        # first legal start, whatever CC it leaves.
                        c = cc(free & ~m) if block_rule == "cc" else 0
                        if c > best:
                            best = c
                            self.start[free, p] = s
                            self.after[free, p] = free & ~m
                self.fits[free, p] = best >= 0
            # Alg. 4's Fragmentation: pack each profile greedily into a
            # working copy that carries over between profiles, adding the
            # leftover free blocks over the profile's size.
            work, total = free, 0.0
            for p in range(NP):
                if self.sizes[p] > bin(work).count("1"):
                    continue
                for m in slots[p]:
                    if m & work == m:
                        work &= ~m
                total += bin(work).count("1") / self.sizes[p]
            self.frag[free] = total


class Grmu:
    """GRMU state and decisions over a homogeneous fleet."""

    def __init__(self, fleet: dict, gpu_counts: np.ndarray, *,
                 heavy_capacity_frac: float, defrag: bool = True,
                 defrag_trigger: str = "light",
                 consolidation_interval: Optional[float] = None,
                 tables: Optional[MigTables] = None):
        self.T = tables or MigTables(fleet)
        G = int(np.sum(gpu_counts))
        H = len(gpu_counts)
        self.G = G
        self.gpu_host = np.repeat(np.arange(H), gpu_counts)
        self.free = np.full(G, self.T.full, np.int64)
        self.cpu_cap = np.full(H, fleet["host_cpu"], np.float32)
        self.ram_cap = np.full(H, fleet["host_ram"], np.float32)
        self.cpu_used = np.zeros(H, np.float32)
        self.ram_used = np.zeros(H, np.float32)
        self.basket = np.full(G, POOL, np.int64)
        if G > 0:
            self.basket[0] = HEAVY
        if G > 1:
            self.basket[1] = LIGHT
        self.heavy_cap = int(round(heavy_capacity_frac * G))
        self.light_cap = G - self.heavy_cap
        self.defrag_on = defrag
        self.defrag_trigger = defrag_trigger
        self.interval = consolidation_interval
        self.last_consolidation = 0.0
        self.on_gpu: List[Dict[int, int]] = [dict() for _ in range(G)]
        self.where: Dict[int, Tuple[int, int]] = {}   # vm -> (gpu, start)
        self.vm_pid: Dict[int, int] = {}
        self.vm_res: Dict[int, Tuple[np.float32, np.float32]] = {}
        self.history: Dict[int, List[Tuple[int, int, int]]] = {}
        self.stamp = 0                 # 2 * step + phase, set by the loop
        self.intra = 0
        self.inter = 0

    # -- Algs. 2-3 -------------------------------------------------------
    def _host_ok(self, c, r) -> np.ndarray:
        ok = ((self.cpu_used + c <= self.cpu_cap)
              & (self.ram_used + r <= self.ram_cap))
        return ok[self.gpu_host]

    def place(self, vm: int, pid: int, cpu: float, ram: float) -> bool:
        c, r = np.float32(cpu), np.float32(ram)
        heavy = pid == self.T.heavy_profile
        want = HEAVY if heavy else LIGHT
        host_ok = self._host_ok(c, r)
        in_basket = self.basket == want
        cand = self.T.fits[self.free, pid] & host_ok & in_basket
        if cand.any():
            g = int(np.argmax(cand))
        else:
            cap = self.heavy_cap if heavy else self.light_cap
            pool = np.flatnonzero(self.basket == POOL)
            if in_basket.sum() >= cap or len(pool) == 0:
                return False
            g = int(pool[0])
            self.basket[g] = want
            if not host_ok[g]:
                return False
        self._assign(vm, g, pid, c, r)
        return True

    def _assign(self, vm, g, pid, c, r) -> None:
        s = int(self.T.start[self.free[g], pid])
        self.free[g] = self.T.after[self.free[g], pid]
        h = self.gpu_host[g]
        self.cpu_used[h] += c
        self.ram_used[h] += r
        self.on_gpu[g][s] = vm
        self.where[vm] = (g, s)
        self.vm_pid[vm] = pid
        self.vm_res[vm] = (c, r)
        self.history.setdefault(vm, []).append((self.stamp, g, s))

    def _blocks(self, pid: int, s: int) -> int:
        return ((1 << self.T.sizes[pid]) - 1) << s

    def release(self, vm: int) -> None:
        g, s = self.where.pop(vm)
        del self.on_gpu[g][s]
        self.free[g] |= self._blocks(self.vm_pid[vm], s)
        c, r = self.vm_res[vm]
        h = self.gpu_host[g]
        self.cpu_used[h] -= c
        self.ram_used[h] -= r

    # -- Alg. 4 ----------------------------------------------------------
    def defragment(self) -> None:
        score = np.where(self.basket == LIGHT, self.T.frag[self.free],
                         np.float32(-1.0))
        g = int(np.argmax(score))
        if not (score[g] > 0.0 and self.free[g] != self.T.full):
            return
        mock, moves = self.T.full, []
        for b in range(self.T.num_blocks):
            vm = self.on_gpu[g].get(b)
            if vm is None:
                continue
            p = self.vm_pid[vm]
            if not self.T.fits[mock, p]:
                return
            ns = int(self.T.start[mock, p])
            mock = int(self.T.after[mock, p])
            moves.append((vm, b, ns))
        moved = sum(1 for _, b, ns in moves if ns != b)
        if moved == 0:
            return
        self.on_gpu[g] = {ns: vm for vm, _, ns in moves}
        for vm, b, ns in moves:
            self.where[vm] = (g, ns)
            if ns != b:
                self.history[vm].append((self.stamp, g, ns))
        self.free[g] = mock
        self.intra += moved

    # -- Alg. 5 ----------------------------------------------------------
    def consolidate(self) -> None:
        T = self.T
        half = (self.free == T.lower_half) | (self.free == T.upper_half)
        cand = np.zeros(self.G, bool)
        for g in np.flatnonzero((self.basket == LIGHT) & half):
            res = self.on_gpu[g]
            if len(res) == 1:
                cand[g] = self.vm_pid[next(iter(res.values()))] \
                    in T.half_profiles
        avail = cand.copy()
        cpu_u, ram_u = self.cpu_used.copy(), self.ram_used.copy()
        gids = np.arange(self.G)
        plan = []
        for g in np.flatnonzero(cand):
            if not avail[g]:
                continue
            vm = next(iter(self.on_gpu[g].values()))
            p = self.vm_pid[vm]
            c, r = self.vm_res[vm]
            h = self.gpu_host[g]
            gh = self.gpu_host
            host_ok = (gh == h) | ((cpu_u[gh] + c <= self.cpu_cap[gh])
                                   & (ram_u[gh] + r <= self.ram_cap[gh]))
            ok = avail & (gids > g) & T.fits[self.free, p] & host_ok
            avail[g] = False
            if not ok.any():
                continue
            t = int(np.argmax(ok))
            avail[t] = False
            th = self.gpu_host[t]
            if th != h:
                cpu_u[h] -= c
                cpu_u[th] += c
                ram_u[h] -= r
                ram_u[th] += r
            plan.append((int(g), t, vm))
        for src, dst, vm in plan:
            if not self._migrate(vm, dst):
                continue
            self.basket[src] = POOL
            self.inter += 1

    def _migrate(self, vm: int, dst: int) -> bool:
        src, _ = self.where[vm]
        p = self.vm_pid[vm]
        c, r = self.vm_res[vm]
        hs, hd = self.gpu_host[src], self.gpu_host[dst]
        if hd != hs and not (self.cpu_used[hd] + c <= self.cpu_cap[hd]
                             and self.ram_used[hd] + r <= self.ram_cap[hd]):
            return False
        if not self.T.fits[self.free[dst], p]:
            return False
        self.release(vm)
        self._assign(vm, dst, p, c, r)
        return True

    # -- engine hooks ----------------------------------------------------
    def step_end(self, now: float, rejected_light: bool,
                 rejected_any: bool) -> None:
        if self.defrag_on and (rejected_light if self.defrag_trigger
                               == "light" else rejected_any):
            self.defragment()
        if (self.interval is not None
                and now - self.last_consolidation >= self.interval):
            self.consolidate()
            self.last_consolidation = now

    def sample(self) -> Tuple[int, int]:
        """(active hosts, active GPUs): a GPU is active when it holds a
        VM, a host when one of its GPUs is."""
        active = self.free != self.T.full
        hosts = np.zeros(len(self.cpu_cap), bool)
        hosts[self.gpu_host[active]] = True
        return int(hosts.sum()), int(active.sum())


def require_grmu(policy: dict) -> None:
    """Only GRMU is written out here; another policy needs its own
    reference before a cell can be held to it."""
    if policy.get("name") != "GRMU":
        raise ValueError(f"the reference implements GRMU only, the "
                         f"configuration names {policy.get('name')!r}")


def simulate(fleet: dict, policy: dict, stream: dict, *,
             n_vms: Optional[int] = None,
             horizon: Optional[float] = None,
             step_hours: float = 1.0,
             tables: Optional[MigTables] = None) -> dict:
    """Run GRMU over the first ``n_vms`` VMs of ``stream`` (all by
    default) in the paper's discrete-time loop, up to ``horizon`` (the
    last arrival plus a step by default).

    Returns per-VM acceptance and stamped placement history
    (``[(stamp, gpu, start), ...]``), per-profile counts,
    the hourly series (cumulative accepted, cumulative offered, active
    hosts, active GPUs) and the migration counts."""
    require_grmu(policy)
    knobs = policy
    sim = Grmu(fleet, stream["gpu_counts"],
               heavy_capacity_frac=knobs["heavy_capacity_frac"],
               defrag=knobs["defrag"],
               defrag_trigger=knobs["defrag_trigger"],
               consolidation_interval=knobs["consolidation_interval"],
               tables=tables)
    n = len(stream["arrival"]) if n_vms is None else int(n_vms)
    arrival = stream["arrival"][:n]
    order = sorted(range(n), key=lambda i: (arrival[i], i))
    if horizon is None:
        horizon = (float(arrival.max()) if n else 0.0) + step_hours
    NP = len(fleet["profiles"])
    accepted = np.zeros(n, bool)
    per_acc = np.zeros(NP, np.int64)
    per_tot = np.zeros(NP, np.int64)
    hourly = []
    departures: list = []
    ai, t = 0, 0.0
    n_acc = n_tot = 0
    k = 0
    while t < horizon + EPS:
        end = t + step_hours
        sim.stamp = 2 * k
        while departures and departures[0][0] <= k:
            vm = heapq.heappop(departures)[2]
            sim.release(vm)
        rej_light = rej_any = False
        while ai < n and arrival_step(arrival[order[ai]], step_hours) <= k:
            vm = order[ai]
            ai += 1
            p = int(stream["pid"][vm])
            n_tot += 1
            per_tot[p] += 1
            if sim.place(vm, p, stream["cpu"][vm], stream["ram"][vm]):
                accepted[vm] = True
                n_acc += 1
                per_acc[p] += 1
                leave = arrival[vm] + stream["duration"][vm]
                heapq.heappush(departures, (
                    release_step(leave, k, step_hours), leave, vm))
            else:
                rej_any = True
                rej_light |= p != sim.T.heavy_profile
        sim.stamp = 2 * k + 1
        sim.step_end(t, rej_light, rej_any)
        pms, gpus = sim.sample()
        hourly.append((n_acc, n_tot, pms, gpus))
        t = end
        k += 1
    return dict(accepted=accepted, history=sim.history,
                per_profile_accepted=per_acc, per_profile_total=per_tot,
                hourly=np.asarray(hourly, np.int64).reshape(-1, 4),
                intra=sim.intra, inter=sim.inter,
                num_hosts=len(stream["gpu_counts"]), num_gpus=sim.G)


__all__ = ["MigTables", "Grmu", "simulate", "require_grmu", "arrival_step",
           "release_step"]
