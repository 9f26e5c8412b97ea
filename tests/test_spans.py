"""The program's one span system (DESIGN.md §12): every span name is
declared, spans stay at batch or stage granularity, records carry their
parent and cause, a device tracer puts them on the profiler's timeline,
and the executor counts what XLA compiled."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch_executor import BatchValidator
from repro.data import doc_table
from repro.data.doc_table import encode_batch
from repro.obs.metrics import MetricRegistry
from repro.obs.trace import POINTS, SPANS, Tracer, span, trace_point
from repro.registry import SchemaRegistry

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

SCHEMA = {
    "type": "object",
    "required": ["a"],
    "properties": {"a": {"type": "integer", "minimum": 0}, "tags": {"type": "array"}},
}
MAX_NODES = 16


def _engine():
    from repro.configs import get_config
    from repro.serve.engine import ServeConfig, ServeEngine

    engine = ServeEngine(
        get_config("phi4-mini-3.8b"),
        None,
        ServeConfig(admission_max_nodes=MAX_NODES),
        registry=SchemaRegistry(use_pallas=False),
    )
    engine.register_endpoint("ep", SCHEMA)
    return engine


def _requests(n):
    """``n`` requests of one endpoint: valid, invalid, one too large for
    the encoder (the sequential fallback takes it) and one not JSON."""
    out = [("ep", json.dumps({"a": i % 7 - 1})) for i in range(n - 2)]
    out.append(("ep", json.dumps({"a": 1, "tags": list(range(2 * MAX_NODES))})))
    out.append(("ep", "{not json"))
    return out


# ---------------------------------------------------------------------------
# the declared set
# ---------------------------------------------------------------------------


def _literals(pattern):
    found = {}
    for path in SRC.rglob("*.py"):
        if path.parent.name == "obs":
            continue
        for name in re.findall(pattern, path.read_text()):
            found.setdefault(name, path.relative_to(SRC).as_posix())
    return found


def test_every_span_literal_under_src_is_declared():
    spans = _literals(r"(?<![\w.])_?span\(\s*\"([^\"]+)\"")
    points = _literals(r"(?<![\w.])_?trace_point\(\s*\"([^\"]+)\"")
    assert spans and points
    assert {n: f for n, f in spans.items() if n not in SPANS} == {}
    assert {n: f for n, f in points.items() if n not in POINTS} == {}
    # every declared name is opened somewhere: the set is not a wish list
    assert set(SPANS) == set(spans) and set(POINTS) == set(points)


def test_one_seam_only():
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        assert "_phase(" not in text, path
        assert "obs.profile" not in text, path


# ---------------------------------------------------------------------------
# records: parent, cause, attributes
# ---------------------------------------------------------------------------


def test_records_carry_parent_and_cause():
    with Tracer() as tr:
        with span("root.a"):
            with span("child") as c:
                trace_point("mark")
                c.set(done=True)
        with span("root.b"):
            pass
    by = {s.name: s for s in tr.recent()}
    a, child, mark, b = by["root.a"], by["child"], by["mark"], by["root.b"]
    assert a.parent_id == 0 and a.cause_id == a.span_id
    assert child.parent_id == a.span_id and child.cause_id == a.span_id
    assert mark.parent_id == child.span_id and mark.cause_id == a.span_id
    assert b.cause_id == b.span_id != a.span_id
    assert child.attrs == {"done": True}
    assert len({s.span_id for s in tr.recent()}) == 4


def test_disarmed_span_takes_attributes_and_drops_them():
    with span("x") as s:
        s.set(anything=1)  # the shared no-op accepts and ignores them


# ---------------------------------------------------------------------------
# granularity: per batch and per link group, never per document or key
# ---------------------------------------------------------------------------


def test_spans_per_submit_batch_do_not_grow_with_the_batch():
    engine = _engine()
    counts = {}
    for n in (64, 1024):
        reqs = _requests(n)
        engine.submit_batch(reqs)  # compiles this batch's launch shape
        with Tracer(capacity=4096) as tr:
            engine.submit_batch(reqs)
        records = tr.recent()
        (root,) = [s for s in records if s.name == "serve.submit_batch"]
        assert all(s.cause_id == root.span_id for s in records)
        counts[n] = sorted(s.name for s in records)
        assert tr.stats()["admit.fallback"].calls == 1
    assert counts[64] == counts[1024]
    assert counts[64].count("executor.launch") == 1  # one link group


def test_armed_encode_batch_hashes_with_the_plain_key_lanes(monkeypatch):
    docs = [{"k": "v" * (i % 40), "n": [i, str(i)]} for i in range(64)]
    plain = encode_batch(docs, max_nodes=MAX_NODES)
    calls = []
    real = doc_table.key_lanes

    def counted(s):
        calls.append(s)
        return real(s)

    monkeypatch.setattr(doc_table, "key_lanes", counted)
    with Tracer() as tr:
        armed = encode_batch(docs, max_nodes=MAX_NODES)
    assert tr.recorded == 0  # no span inside the encoder
    # two keys and two strings per document, each hashed once
    assert len(calls) == 4 * len(docs)
    for k, v in plain.columns().items():
        np.testing.assert_array_equal(v, armed.columns()[k])


# ---------------------------------------------------------------------------
# the profiler's timeline
# ---------------------------------------------------------------------------


def test_device_tracer_puts_spans_on_the_profiler_timeline(tmp_path):
    """What a ``--trace 1`` run records: the harness's ``bench.submit_batch``
    annotation, with the program's spans from the tracer it arms through
    ``obs/profile.py`` nested inside it on the profiler's host plane."""
    from jax.profiler import ProfileData

    from repro.obs.profile import Profiler, set_profiler

    engine = _engine()
    reqs = _requests(64)
    engine.submit_batch(reqs)
    logdir = str(tmp_path)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)
    prof = Profiler()
    set_profiler(prof)
    try:
        with jax.profiler.TraceAnnotation("bench.submit_batch"):
            engine.submit_batch(reqs)
    finally:
        set_profiler(None)
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS or e.name == "bench.submit_batch":
                    events.setdefault(e.name, []).append((int(e.start_ns), int(e.start_ns + e.duration_ns)))
    (outer,) = events.pop("bench.submit_batch")
    want = {"serve.submit_batch", "serve.parse", "serve.validate", "admit.guard", "admit.encode",
            "admit.launch", "executor.launch", "admit.verdicts", "admit.fallback", "serve.dispatch"}
    assert set(events) == want
    for spans in events.values():
        for s, e in spans:
            assert outer[0] <= s <= e <= outer[1]
    # the same spans as the aggregate the harness copies into its record
    assert {k: v.calls for k, v in prof.stats().items()} == {k: len(v) for k, v in events.items()}
    (launch,), (admit_launch,) = events["executor.launch"], events["admit.launch"]
    assert admit_launch[0] <= launch[0] <= launch[1] <= admit_launch[1]


def test_harness_record_fields_from_the_tracer_aggregate():
    from repro.obs.profile import Profiler, set_profiler

    prof = Profiler()
    assert prof.device
    set_profiler(prof)
    try:
        with span("admit.encode"):
            with span("executor.launch"):
                pass
    finally:
        set_profiler(None)
    phases = {k: v.as_dict() for k, v in prof.stats().items()}
    assert set(phases) == {"admit.encode", "executor.launch"}
    enc = phases["admit.encode"]
    assert set(enc) == {"calls", "total_ns", "self_ns"}
    assert enc["calls"] == 1 and enc["total_ns"] - enc["self_ns"] == phases["executor.launch"]["total_ns"]


# ---------------------------------------------------------------------------
# the executor: named programs and a compile counter that counts compiles
# ---------------------------------------------------------------------------


def _validator(metrics=None):
    reg = SchemaRegistry()
    reg.register("t", SCHEMA)
    return BatchValidator(reg.get("t").tape, use_pallas=False, metrics=metrics)


def _table(n=8):
    return encode_batch([{"a": i - 2} for i in range(n)], max_nodes=MAX_NODES)


def test_launch_and_explain_programs_are_named():
    bv = _validator()
    table = _table()
    cols = {k: jnp.asarray(v) for k, v in table.columns().items()}
    ids = jnp.zeros(table.batch, jnp.int32)
    assert "module @jit__validate_batch " in bv._fn.lower(cols, ids).as_text()
    bv.explain_batch(table)
    assert "module @jit__explain_batch " in bv._explain_fn.lower(cols, ids).as_text()


def test_compile_counter_counts_what_xla_compiled():
    m = MetricRegistry()
    bv = _validator(m)
    compiles = lambda kind: m.counter("executor_compiles_total", kind=kind).value
    with Tracer() as tr:
        bv.validate(_table(8))
        first = {k: compiles(k) for k in ("trace", "compile", "cache_read")}
        bv.validate(_table(8))  # the same shape: nothing to compile
        again = {k: compiles(k) for k in ("trace", "compile", "cache_read")}
        bv.validate(_table(4))  # a new shape compiles again
    assert first["compile"] >= 1 and first["trace"] >= 1
    assert again == first
    assert compiles("compile") > first["compile"]
    assert m.counter("executor_recompiles_total").value == compiles("compile")
    launches = [s for s in tr.recent() if s.name == "executor.launch"]
    assert [s.attrs["compiled"] for s in launches] == [True, False, True]
    points = [s for s in tr.recent() if s.name == "executor.recompile"]
    assert [p.attrs["shape"] for p in points] == [(8, MAX_NODES), (4, MAX_NODES)]
    assert all(p.parent_id in {s.span_id for s in launches} for p in points)

