"""The readers of the served span tree (``serve.queue_wait_ms``,
``serve.pop_ms``, ``serve.ingest_ms``, ``serve.step_ms``,
``serve.readback_ms``, ``serve.emit_ms``, ``serve.batch_fill``) on a
synthetic run with known answers: only the batches of the traced part
count, and a program that records no tree reads nothing."""
import pytest

import benchtest  # noqa: F401  (paths)


def _batch_spans(next_id, batch, rows, arrivals, waits, durs):
    """One served micro-batch as the program records it: the children
    before their parent, as spans close."""
    pop_, ingest, step, readback, emit = durs
    ids = range(next_id, next_id + 7)
    root, pop, rt, ing, stp, rb, em = ids
    spans = [{"name": "serve.pop", "id": pop, "parent": root,
              "dur_s": pop_, "rows": rows, "arrivals": arrivals,
              "wait_sum_s": sum(waits), "batch_rows": 64}]
    if arrivals:
        spans.append({"name": "serve.ingest", "id": ing, "parent": rt,
                      "dur_s": ingest})
    spans += [{"name": "serve.step", "id": stp, "parent": rt,
               "dur_s": step},
              {"name": "serve.readback", "id": rb, "parent": rt,
               "dur_s": readback},
              {"name": "serve.batch", "id": rt, "parent": root,
               "dur_s": (ingest if arrivals else 0) + step + readback},
              {"name": "serve.emit", "id": em, "parent": root,
               "dur_s": emit},
              {"name": "serve.drain_batch", "id": root, "parent": None,
               "batch": batch, "dur_s": 0.02}]
    return spans


def _served_run():
    """Three drains, the profiler stopped after the second: the third
    batch ran behind the stall and is left out.  The second batch holds
    no arrival, so it runs no ingest."""
    from types import SimpleNamespace
    spans = (_batch_spans(0, 0, 16, 4, [0.001, 0.002, 0.003, 0.006],
                          (0.0010, 0.0020, 0.0010, 0.0040, 0.0005))
             + _batch_spans(7, 1, 8, 0, [],
                            (0.0010, 0.0, 0.0020, 0.0040, 0.0010))
             + _batch_spans(14, 2, 64, 10, [1.0] * 10,
                            (0.5, 0.5, 0.5, 0.5, 0.5)))
    return SimpleNamespace(
        spans=[("bench.drain", 0.0, 0.010), ("bench.wait_arrival", 0.010,
                                             0.011),
               ("bench.drain", 0.011, 0.019), ("bench.drain", 5.0, 5.5)],
        program_spans=spans, trace_closed=1.0)


SERVED_BATCH_READS = {
    "serve.queue_wait_ms": 3.0,         # 12 ms of waits over 4 arrivals
    "serve.pop_ms": 1.0,
    "serve.ingest_ms": 1.0,             # one ingest of 2 ms, two batches
    "serve.step_ms": 1.5,
    "serve.readback_ms": 4.0,
    "serve.emit_ms": 0.75,
    "serve.batch_fill": 100.0 * 24 / 128,
}


@pytest.mark.parametrize("name", sorted(SERVED_BATCH_READS))
def test_served_batch_readers(name):
    from types import SimpleNamespace
    from benchlib import spec
    read = spec.metric_reader(name)
    run = _served_run()
    assert read(run) == pytest.approx(SERVED_BATCH_READS[name])
    # A program that records only ````serve.batch```` (no span tree) reads
    # nothing, and so does a run whose window held no drain.
    old = SimpleNamespace(
        spans=run.spans, trace_closed=run.trace_closed,
        program_spans=[{"name": "serve.batch", "dur_s": d}
                       for d in (0.006, 0.005, 0.4)])
    assert read(old) is None
    assert read(SimpleNamespace(spans=[], program_spans=run.program_spans,
                                trace_closed=1.0)) is None


def test_served_batch_readers_split_the_round_trip():
    """Ingest, step and read-back add up to ````serve.roundtrip_ms````, and
    pop and emit fit inside ````serve.assembly_ms````, on the same run."""
    from benchlib import spec
    run = _served_run()
    r = {n: spec.metric_reader(n)(run) for n in
         ("serve.ingest_ms", "serve.step_ms", "serve.readback_ms",
          "serve.pop_ms", "serve.emit_ms")}
    # The older readers take the serve.batch spans in the order they
    # closed, as they always did.
    run.program_spans = [s for s in run.program_spans
                         if s["name"] != "serve.drain_batch"]
    rt = spec.metric_reader("serve.roundtrip_ms")(run)
    assert r["serve.ingest_ms"] + r["serve.step_ms"] \
        + r["serve.readback_ms"] == pytest.approx(rt)
    assert r["serve.pop_ms"] + r["serve.emit_ms"] \
        <= spec.metric_reader("serve.assembly_ms")(run)
