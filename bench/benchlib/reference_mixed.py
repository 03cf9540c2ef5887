"""Plain sequential GRMU on a fleet that mixes MIG device generations:
the reference of ``openb-mixed-grmu``.

``reference.py`` reads the paper's Algorithms 1-5 on one device model;
this reads them on several, one VM at a time, with no batching, padding
or device.  It keeps one ``reference.MigTables`` per model (Alg. 1 and
the fragmentation metric over that model's free masks), each GPU's model
index, and each VM's profile on every model (``stream_mixed``'s ``pid``
rows).  Like ``reference.py`` it imports nothing of the program under
test and reads only the generated stream and the configuration file.

The paper writes GRMU for one A100-40GB fleet.  Where several models
share the fleet, this follows the extension ``core/grmu.py`` documents,
and departs from the paper's text in these places:

  * heavy rule (Alg. 2): a request is heavy, and goes to the heavy
    basket, only if it maps to the full-GPU profile on every model; any
    other request is light, and its rejection triggers defragmentation;
  * fit and placement (Algs. 1 and 3): each GPU is tested with the
    request's profile on that GPU's own model, and Alg. 1 runs on that
    model's profile table and CC;
  * defragmentation (Alg. 4): the light GPU with the highest
    fragmentation, each scored on its own model's table, is re-packed
    through its own model's Alg. 1, with each resident's profile on that
    model; a GPU is empty when all of its own model's blocks are free;
  * consolidation (Alg. 5): a source is half-full on its own model and
    holds one VM whose profile on that model takes half of it; each
    candidate target is tested with the source VM's profile on the
    target's model, and the VM lands through the target's Alg. 1;
  * the per-profile counts, the stream's profile mix and each VM's CPU
    and RAM use the first model of ``fleet.devices`` (the reference
    device); an active GPU is one that holds a VM, on any model.

On a one-model ``devices`` list every rule reduces to the paper's, and
the results equal ``reference.simulate``'s.
"""
from __future__ import annotations

import heapq
from typing import Optional, Sequence

import numpy as np

from . import reference
from .reference import EPS, HEAVY, LIGHT, POOL


class MixedGrmu(reference.Grmu):
    """GRMU state and decisions over a fleet of several device models.
    ``vm_pid`` holds each resident VM's profile on every model."""

    def __init__(self, fleet: dict, gpu_counts: np.ndarray,
                 host_model: np.ndarray, *,
                 tables: Optional[Sequence[reference.MigTables]] = None,
                 **knobs):
        self.Ts = list(tables or [reference.MigTables(d)
                                  for d in fleet["devices"]])
        super().__init__(fleet, gpu_counts, tables=self.Ts[0], **knobs)
        self.mid = np.repeat(np.asarray(host_model), gpu_counts)
        self.gpus_of = [np.flatnonzero(self.mid == m)
                        for m in range(len(self.Ts))]
        self.full = np.array([t.full for t in self.Ts], np.int64)[self.mid]
        self.free = self.full.copy()

    def _T(self, g: int) -> reference.MigTables:
        return self.Ts[self.mid[g]]

    def _fits(self, pids) -> np.ndarray:
        """Per GPU: the request fits, tested with its profile on the
        GPU's own model."""
        out = np.zeros(self.G, bool)
        for m, (T, gs) in enumerate(zip(self.Ts, self.gpus_of)):
            out[gs] = T.fits[self.free[gs], pids[m]]
        return out

    def _frag(self) -> np.ndarray:
        out = np.zeros(self.G, np.float32)
        for T, gs in zip(self.Ts, self.gpus_of):
            out[gs] = T.frag[self.free[gs]]
        return out

    def is_heavy(self, pids) -> bool:
        return all(T.heavy_profile >= 0 and int(p) == T.heavy_profile
                   for T, p in zip(self.Ts, pids))

    # -- Algs. 2-3 -------------------------------------------------------
    def place(self, vm: int, pids, cpu: float, ram: float) -> bool:
        c, r = np.float32(cpu), np.float32(ram)
        heavy = self.is_heavy(pids)
        want = HEAVY if heavy else LIGHT
        host_ok = self._host_ok(c, r)
        in_basket = self.basket == want
        cand = self._fits(pids) & host_ok & in_basket
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
        self._assign(vm, g, tuple(int(p) for p in pids), c, r)
        return True

    def _assign(self, vm, g, pids, c, r) -> None:
        T, p = self._T(g), pids[self.mid[g]]
        s = int(T.start[self.free[g], p])
        self.free[g] = T.after[self.free[g], p]
        h = self.gpu_host[g]
        self.cpu_used[h] += c
        self.ram_used[h] += r
        self.on_gpu[g][s] = vm
        self.where[vm] = (g, s)
        self.vm_pid[vm] = pids
        self.vm_res[vm] = (c, r)
        self.history.setdefault(vm, []).append((self.stamp, g, s))

    def release(self, vm: int) -> None:
        g, s = self.where.pop(vm)
        del self.on_gpu[g][s]
        size = self._T(g).sizes[self.vm_pid[vm][self.mid[g]]]
        self.free[g] |= ((1 << size) - 1) << s
        c, r = self.vm_res[vm]
        h = self.gpu_host[g]
        self.cpu_used[h] -= c
        self.ram_used[h] -= r

    # -- Alg. 4 ----------------------------------------------------------
    def defragment(self) -> None:
        score = np.where(self.basket == LIGHT, self._frag(),
                         np.float32(-1.0))
        g = int(np.argmax(score))
        if not (score[g] > 0.0 and self.free[g] != self.full[g]):
            return
        T, m = self._T(g), self.mid[g]
        mock, moves = T.full, []
        for b in range(T.num_blocks):
            vm = self.on_gpu[g].get(b)
            if vm is None:
                continue
            p = self.vm_pid[vm][m]
            if not T.fits[mock, p]:
                return
            ns = int(T.start[mock, p])
            mock = int(T.after[mock, p])
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
        cand = np.zeros(self.G, bool)
        for g in np.flatnonzero(self.basket == LIGHT):
            T, res = self._T(g), self.on_gpu[g]
            if (self.free[g] in (T.lower_half, T.upper_half)
                    and len(res) == 1):
                vm = next(iter(res.values()))
                cand[g] = self.vm_pid[vm][self.mid[g]] in T.half_profiles
        avail = cand.copy()
        cpu_u, ram_u = self.cpu_used.copy(), self.ram_used.copy()
        gids = np.arange(self.G)
        plan = []
        for g in np.flatnonzero(cand):
            if not avail[g]:
                continue
            vm = next(iter(self.on_gpu[g].values()))
            c, r = self.vm_res[vm]
            h = self.gpu_host[g]
            gh = self.gpu_host
            host_ok = (gh == h) | ((cpu_u[gh] + c <= self.cpu_cap[gh])
                                   & (ram_u[gh] + r <= self.ram_cap[gh]))
            ok = avail & (gids > g) & self._fits(self.vm_pid[vm]) & host_ok
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
        pids = self.vm_pid[vm]
        c, r = self.vm_res[vm]
        hs, hd = self.gpu_host[src], self.gpu_host[dst]
        if hd != hs and not (self.cpu_used[hd] + c <= self.cpu_cap[hd]
                             and self.ram_used[hd] + r <= self.ram_cap[hd]):
            return False
        if not self._T(dst).fits[self.free[dst], pids[self.mid[dst]]]:
            return False
        self.release(vm)
        self._assign(vm, dst, pids, c, r)
        return True

    def sample(self):
        active = self.free != self.full
        hosts = np.zeros(len(self.cpu_cap), bool)
        hosts[self.gpu_host[active]] = True
        return int(hosts.sum()), int(active.sum())


def simulate(fleet: dict, policy: dict, stream: dict, *,
             n_vms: Optional[int] = None,
             horizon: Optional[float] = None,
             step_hours: float = 1.0,
             tables: Optional[Sequence[reference.MigTables]] = None
             ) -> dict:
    """``reference.simulate`` on a mixed fleet: ``stream`` is
    ``stream_mixed.generate``'s (``pid`` rows per model, ``host_model``),
    and the result has the same keys, with per-profile counts on the
    reference device's profiles."""
    reference.require_grmu(policy)
    sim = MixedGrmu(fleet, stream["gpu_counts"], stream["host_model"],
                    heavy_capacity_frac=policy["heavy_capacity_frac"],
                    defrag=policy["defrag"],
                    defrag_trigger=policy["defrag_trigger"],
                    consolidation_interval=policy["consolidation_interval"],
                    tables=tables)
    n = len(stream["arrival"]) if n_vms is None else int(n_vms)
    arrival = stream["arrival"][:n]
    pid = np.asarray(stream["pid"]).reshape(len(stream["arrival"]), -1)
    order = sorted(range(n), key=lambda i: (arrival[i], i))
    if horizon is None:
        horizon = (float(arrival.max()) if n else 0.0) + step_hours
    NP = len(fleet["devices"][0]["profiles"])
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
        while ai < n and reference.arrival_step(
                arrival[order[ai]], step_hours) <= k:
            vm = order[ai]
            ai += 1
            p = int(pid[vm, 0])
            n_tot += 1
            per_tot[p] += 1
            if sim.place(vm, pid[vm], stream["cpu"][vm], stream["ram"][vm]):
                accepted[vm] = True
                n_acc += 1
                per_acc[p] += 1
                leave = arrival[vm] + stream["duration"][vm]
                heapq.heappush(departures, (
                    reference.release_step(leave, k, step_hours), leave,
                    vm))
            else:
                rej_any = True
                rej_light |= not sim.is_heavy(pid[vm])
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


__all__ = ["MixedGrmu", "simulate"]
