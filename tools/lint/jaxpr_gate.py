"""Layer 2 of repro-lint: jaxpr-level invariants of the replay engine.

The AST rules catch textual hazards; this gate checks what the tracer
actually builds.  Every registry policy's batched step (plain scan,
chunk-streamed step, and K=2 fleet-sharded scan) is traced with
``jax.make_jaxpr`` on a tiny mixed A30+A100+H100 fixture, and three
invariants are asserted on the resulting jaxprs:

1. **No 64-bit values.**  No ``convert_element_type`` to a 64-bit dtype
   and no 64-bit aval anywhere in the (recursively walked) jaxpr —
   in-scan decision state is int32/float32 by contract.  Because x64 is
   disabled, a stray ``astype(jnp.int64)`` is a *silent no-op* that
   leaves no trace in the jaxpr; the gate therefore also records the
   "Explicitly requested dtype ... is not available" truncation warnings
   jax emits during tracing and fails on those too.
2. **No new ``while`` primitives in the scan body.**  The only sanctioned
   sequential loop is MECC's two-pointer window expiry; each baseline
   entry pins the variant's ``while`` count and the gate fails if it
   grows (a nested data-dependent loop would serialize the scan body).
3. **Stable structural fingerprint.**  The primitive-count multiset plus
   the aval dtype set must match ``tools/lint/baselines.json``
   (regenerate deliberately with ``--update-baselines``).  Fingerprints
   are jax-version-sensitive, so the baseline records the jax version it
   was traced under; under a different jax the fingerprint comparison is
   reported as informational only while invariants 1-2 stay hard.

Run via ``python -m tools.lint`` (which forces
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` before importing
jax so the sharded variant traces on CPU).
"""
from __future__ import annotations

import functools
import json
import re
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BASELINES_PATH = Path(__file__).with_name("baselines.json")

VARIANTS = ("plain", "chunked", "sharded")
CHUNK_EVENTS = 16          # pow2, smaller than the fixture's padded E
NUM_SHARDS = 2

WIDE_DTYPES = {"int64", "uint64", "float64", "complex128"}
_TRUNCATION_RE = re.compile(
    r"Explicitly requested dtype.*(int64|uint64|float64|complex128)")


# ---------------------------------------------------------------------------
# Fixture
# ---------------------------------------------------------------------------

def mixed_fixture():
    """Tiny deterministic mixed-fleet trace: 8 VMs over 6 GPUs (2 each of
    A30-24GB / A100-40GB / H100-80GB) on 3 hosts — enough to exercise
    hetero per-model profile gathers, host caps and every event kind."""
    import numpy as np
    from repro.core.batched import build_events_arrays
    from repro.core.mig import DEVICE_MODELS
    from repro.workload.alibaba import map_gpu_requirement_to_profile

    models = tuple(DEVICE_MODELS[n]
                   for n in ("A30-24GB", "A100-40GB", "H100-80GB"))
    u = np.array([0.10, 0.22, 0.48, 1.00, 0.30, 0.60, 0.14, 1.00])
    pids = np.stack(
        [map_gpu_requirement_to_profile(u, u_max=1.0, model=m)
         for m in models], axis=1).astype(np.int16)
    n = len(u)
    return build_events_arrays(
        arrival=np.array([0.2, 0.4, 1.1, 1.3, 2.2, 2.4, 3.1, 3.3]),
        duration=np.array([2.0, 5.0, 2.0, 3.0, 1.0, 2.0, 1.0, 1.0]),
        cpu=np.full(n, 2.0, np.float32),
        ram=np.full(n, 8.0, np.float32),
        vm_ids=np.arange(n),
        pids=pids,
        models=models,
        gpu_model_id=np.array([0, 1, 2, 0, 1, 2], np.int32),
        gpu_host_id=np.array([0, 0, 1, 1, 2, 2], np.int32),
        cpu_cap=np.full(3, 32.0, np.float32),
        ram_cap=np.full(3, 128.0, np.float32))


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------

def _policy_statics_kwargs(policy_name: str) -> dict:
    # GRMU with defrag on traces the cond/defrag branch too; keep
    # consolidation off (interval=None) to match the sweep default.
    return {"defrag": True} if policy_name == "GRMU" else {}


def trace_variant(events, policy_id: int, policy_name: str,
                  variant: str):
    """(closed_jaxpr, truncation_warnings) for one policy x variant."""
    import jax
    import numpy as np
    from repro.core import sharded as SH
    from repro.core.batched import (_scan_fn, init_state, replay_statics,
                                    trace_arrays)
    from repro.core.bucketing import pad_events
    from repro.core.streaming import _chunk_fn, split_trace

    kw = _policy_statics_kwargs(policy_name)
    cap = np.int32(2)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        if variant == "plain":
            ev = pad_events(events)
            st = replay_statics(ev, policy_id, score_backend="tables",
                                **kw)
            closed = jax.make_jaxpr(functools.partial(_scan_fn, st))(
                init_state(ev, st), trace_arrays(ev), cap)
        elif variant == "chunked":
            ev = pad_events(events, event_multiple=CHUNK_EVENTS)
            st = replay_statics(ev, policy_id, score_backend="tables",
                                **kw)
            ev_np, rest = split_trace(trace_arrays(ev))
            chunk = {k: v[:CHUNK_EVENTS] for k, v in ev_np.items()}
            closed = jax.make_jaxpr(functools.partial(_chunk_fn, st))(
                init_state(ev, st), chunk, rest, cap)
        elif variant == "sharded":
            from jax.sharding import PartitionSpec as P
            if len(jax.devices()) < NUM_SHARDS:
                raise RuntimeError(
                    f"sharded variant needs {NUM_SHARDS} devices; run "
                    "via `python -m tools.lint` (it sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count)")
            ev = pad_events(events, shards=NUM_SHARDS)
            mesh = SH.fleet_mesh(NUM_SHARDS)
            st = replay_statics(ev, policy_id, score_backend="tables",
                                axis_name=SH.FLEET_AXIS,
                                num_shards=NUM_SHARDS, **kw)
            body = jax.shard_map(functools.partial(_scan_fn, st),
                                 mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=P(), check_vma=False)
            closed = jax.make_jaxpr(body)(
                init_state(ev, st), trace_arrays(ev), cap)
        else:
            raise ValueError(f"unknown variant {variant!r}")
    truncations = [str(w.message) for w in caught
                   if _TRUNCATION_RE.search(str(w.message))]
    return closed, truncations


# ---------------------------------------------------------------------------
# Fingerprinting
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    """Duck-typed sub-jaxpr discovery inside eqn params (cond branches,
    scan/while bodies, pjit/shard_map inner jaxprs, custom calls)."""
    for v in params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(item, "jaxpr"):          # ClosedJaxpr
                yield item.jaxpr
            elif hasattr(item, "eqns"):         # raw Jaxpr
                yield item


def _walk(jaxpr, ops: Dict[str, int], dtypes: set,
          wide: List[str]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ops[name] = ops.get(name, 0) + 1
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            dtypes.add(str(dt))
            if str(dt) in WIDE_DTYPES:
                wide.append(f"{name}: {dt} aval")
        if name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if new in WIDE_DTYPES:
                wide.append(f"convert_element_type -> {new}")
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, ops, dtypes, wide)


def fingerprint(closed) -> dict:
    """Structural fingerprint of a ClosedJaxpr: primitive-count multiset,
    aval dtype set, while-primitive count, and 64-bit evidence."""
    ops: Dict[str, int] = {}
    dtypes: set = set()
    wide: List[str] = []
    _walk(closed.jaxpr, ops, dtypes, wide)
    for const in closed.consts:
        dt = getattr(const, "dtype", None)
        if dt is not None and str(dt) in WIDE_DTYPES:
            wide.append(f"const: {dt}")
    return {"ops": dict(sorted(ops.items())),
            "dtypes": sorted(dtypes),
            "num_while": ops.get("while", 0),
            "wide": wide}


# ---------------------------------------------------------------------------
# Baselines + gate
# ---------------------------------------------------------------------------

def load_baselines(path: Path = BASELINES_PATH) -> Optional[dict]:
    if not path.exists():
        return None
    return json.loads(path.read_text())


def save_baselines(entries: Dict[str, dict],
                   path: Path = BASELINES_PATH) -> None:
    import jax
    import numpy as np
    payload = {
        "_comment": ("repro-lint jaxpr fingerprints; regenerate with "
                     "`python -m tools.lint --update-baselines` and "
                     "review the diff (op-count drift = the replay "
                     "compiles differently than the pinned engine)."),
        "jax_version": jax.__version__,
        "numpy_version": np.__version__,
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")


def run_gate(update: bool = False,
             variants: Tuple[str, ...] = VARIANTS,
             baselines_path: Path = BASELINES_PATH
             ) -> Tuple[List[str], List[str], Dict[str, dict]]:
    """Trace every policy x variant and compare against the baselines.

    Returns (errors, notes, results); with ``update=True`` the traced
    fingerprints are written back as the new baselines (errors then only
    cover the hard 64-bit / truncation invariants).
    """
    import jax
    from repro.core import policy_core as pc

    errors: List[str] = []
    notes: List[str] = []
    results: Dict[str, dict] = {}
    events = mixed_fixture()

    baselines = load_baselines(baselines_path)
    base_entries = (baselines or {}).get("entries", {})
    base_jax = (baselines or {}).get("jax_version")
    same_jax = base_jax == jax.__version__
    if baselines is not None and not same_jax:
        notes.append(
            f"baselines traced under jax {base_jax}, running "
            f"{jax.__version__}: fingerprint equality reported as "
            "informational only (64-bit and while-count invariants "
            "remain hard); re-pin with --update-baselines")

    for policy_name, policy_id in sorted(pc.POLICY_IDS.items(),
                                         key=lambda kv: kv[1]):
        for variant in variants:
            key = f"{policy_name}:{variant}"
            closed, truncations = trace_variant(
                events, policy_id, policy_name, variant)
            fp = fingerprint(closed)
            results[key] = fp
            # Hard invariant 1: no 64-bit values, traced or truncated.
            for w in fp["wide"]:
                errors.append(f"{key}: 64-bit value in jaxpr ({w})")
            for msg in truncations:
                errors.append(
                    f"{key}: 64-bit astype truncated during tracing "
                    f"(x64 is disabled, so this is a silent no-op in "
                    f"the jaxpr): {msg.splitlines()[0]}")
            if update:
                continue
            base = base_entries.get(key)
            if base is None:
                errors.append(
                    f"{key}: no baseline pinned — run "
                    "`python -m tools.lint --update-baselines`")
                continue
            # Hard invariant 2: while count may not grow.
            if fp["num_while"] > base["num_while"]:
                errors.append(
                    f"{key}: {fp['num_while']} while primitive(s) in "
                    f"the traced step, baseline pins "
                    f"{base['num_while']} — a new data-dependent loop "
                    "serializes the scan body")
            # Invariant 3: structural fingerprint (hard iff same jax).
            mismatch = []
            if fp["ops"] != base["ops"]:
                drift = {
                    op: (base["ops"].get(op, 0), fp["ops"].get(op, 0))
                    for op in set(base["ops"]) | set(fp["ops"])
                    if base["ops"].get(op, 0) != fp["ops"].get(op, 0)}
                mismatch.append(f"op counts drifted {drift}")
            if fp["dtypes"] != base["dtypes"]:
                mismatch.append(
                    f"dtype set drifted {base['dtypes']} -> "
                    f"{fp['dtypes']}")
            if mismatch:
                msg = f"{key}: fingerprint mismatch ({'; '.join(mismatch)})"
                if same_jax:
                    errors.append(msg)
                else:
                    notes.append(msg + " [jax version differs]")

    if update:
        entries = {k: {kk: v[kk] for kk in ("ops", "dtypes", "num_while")}
                   for k, v in results.items()}
        save_baselines(entries, baselines_path)
        notes.append(f"baselines written: {baselines_path} "
                     f"({len(entries)} entries)")
    return errors, notes, results
