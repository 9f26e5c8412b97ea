#!/usr/bin/env python3
"""Smoke run of the gateway's main path on one TPU chip.

    python3 chip_smoke.py [--seed N]

Drives the path a gateway user calls -- register the endpoint schemas,
offer requests to the stream scheduler, validate them in batched Pallas
launches, explain the rejects, decode the admitted prompts -- once, at
the published widths of ``phi4-mini-3.8b`` with random weights made from
``--seed``.  Checks what comes out against the plain references:

- every decided verdict equals ``NaiveValidator``'s;
- the Pallas launch and the ``jax.numpy`` launch give bit-identical
  ``(valid, decided)``, and the compiled launch holds a Pallas kernel;
- at least one drain went batched and no row was ERROR_ISOLATED;
- every ``explain_batch`` site is a location the sequential trace blames;
- decode logits are finite and every decoded request completes.

One process, one chip, no child processes.  Exits non-zero with no
result line when JAX finds no TPU or any check fails; otherwise the last
line of stdout is ``{"ok": true, "device": {...}}``.  This is a smoke,
not a benchmark: the times it prints include compilation.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import json
import random
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "phi4-mini-3.8b"
N_DOCS = 4096  # stream offers and the closed-loop launch batch
MAX_NODES = 64  # token-table width of the gateway benchmark
DRAIN_BATCH = 256  # scheduler lane cap, pre-traced per link group
DECODE_REQUESTS = 4
DECODE_TOKENS = 8


class SmokeError(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


class Phases:
    """Wall and compile seconds per named phase.

    Compile seconds sum the backend compiles (XLA and Mosaic, reads of
    the persistent cache included) that JAX reports while the phase
    runs; tracing and lowering stay in the wall time only.
    """

    def __init__(self):
        import jax

        self.current = None
        self.wall_s = {}
        self.compile_s = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self.current is not None and event == "/jax/core/compile/backend_compile_duration":
            self.compile_s[self.current] = self.compile_s.get(self.current, 0.0) + duration

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.current = name
        self.compile_s.setdefault(name, 0.0)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.wall_s[name] = time.perf_counter() - t0
            self.current = None


def _drop(key):
    def breaks(doc):
        doc.pop(key)

    return breaks


def _put(key, value):
    def breaks(doc):
        doc[key] = value

    return breaks


def _bad_role(doc):
    doc["messages"][0]["role"] = "robot"


# one way per keyword kind to break each gateway endpoint's schema
BREAKS = {
    "complete": [_drop("prompt"), _put("max_tokens", 5000)],
    "chat": [_drop("messages"), _put("max_tokens", 0), _bad_role],
    "embed": [_drop("input"), _put("dimensions", 4)],
    "moderate": [_drop("category"), _put("category", "other")],
    "charge": [_drop("amount"), _put("amount", 0), _put("currency", "yen")],
}


def gateway_docs(n: int, seed: int, naive):
    """The gateway benchmark's seeded mix, with a seeded quarter of its
    valid documents broken at one keyword each (a required key, a bound
    or an enum).  Every invalid document then fails one keyword only, so
    the sequential trace, which stops at the first failure, blames the
    site that the batched explain picks."""
    from benchmarks.registry import _mixed_stream

    rng = random.Random(seed)
    docs, endpoints = _mixed_stream(n, rng)
    valid = [i for i in range(n) if naive[endpoints[i]].is_valid(docs[i])]
    for i in rng.sample(valid, n // 4):
        docs[i] = copy.deepcopy(docs[i])
        rng.choice(BREAKS[endpoints[i]])(docs[i])
    return docs, endpoints


def launch_has_kernel(validator, table, ids) -> bool:
    """Whether the compiled launch for ``table``'s shape holds a Pallas
    (Mosaic) kernel rather than an interpreted or jnp lowering."""
    import jax.numpy as jnp

    cols = {k: jnp.asarray(v) for k, v in table.columns().items()}
    text = validator._fn.lower(cols, jnp.asarray(ids)).compile().as_text()
    return "tpu_custom_call" in text


def run(cfg, *, seed: int, n_docs: int = N_DOCS) -> dict:
    """Every phase and check; returns what the caller prints."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import NaiveValidator
    from repro.core.batch_executor import BatchValidator
    from repro.core.outcomes import ValidationOutcome
    from repro.data.doc_table import encode_batch
    from repro.models import Model
    from repro.registry import SchemaRegistry
    from repro.registry.presets import GATEWAY_SCHEMAS
    from repro.serve.engine import ServeConfig, ServeEngine

    phase = Phases()
    out: dict = {}

    with phase("init"):
        params = jax.block_until_ready(jax.jit(Model(cfg).init)(jax.random.PRNGKey(seed)))
        engine = ServeEngine(
            cfg,
            params,
            ServeConfig(batch_slots=DECODE_REQUESTS, max_len=256, admission_max_nodes=MAX_NODES),
            endpoint_schemas=GATEWAY_SCHEMAS,
            registry=SchemaRegistry(use_pallas=True),
        )
    reg = engine.registry
    naive = {ep: NaiveValidator(schema) for ep, schema in GATEWAY_SCHEMAS.items()}
    docs, endpoints = gateway_docs(n_docs, seed, naive)
    expected = np.array([naive[ep].is_valid(d) for ep, d in zip(endpoints, docs)])
    check(0 < expected.sum() < n_docs, "the mix must hold valid and invalid documents")

    # -- stream admission: offer -> lanes -> batched drains --------------------
    with phase("warm"):
        sched = engine.scheduler(
            route="batched",
            bench_priors=None,
            max_batch=DRAIN_BATCH,
            warm_shapes=(DRAIN_BATCH,),
        )
    with phase("stream"):
        tickets = [sched.offer(ep, json.dumps(d)) for ep, d in zip(endpoints, docs)]
        sched.flush()
    outcomes = {o.value: 0 for o in ValidationOutcome}
    for i, t in enumerate(tickets):
        check(t.done, f"request {i} has no verdict after flush")
        outcome = t.result.outcome
        outcomes[outcome.value] += 1
        check(
            outcome in (ValidationOutcome.ADMITTED, ValidationOutcome.INVALID),
            f"request {i} ({endpoints[i]}): {outcome.value}: {t.result.error}",
        )
        check(
            (outcome is ValidationOutcome.ADMITTED) == bool(expected[i]),
            f"stream verdict {outcome.value} != NaiveValidator on request {i}",
        )
    out["outcomes"] = outcomes
    out["stream_batched_share"] = engine.stats.batch_validated / n_docs
    out["drains_by_route"] = dict(sched.stats.routed)
    check(sched.stats.routed["batched"] > 0, "no drain was routed batched")

    # -- closed loop: one B=n_docs launch, Pallas against jnp -----------------
    table = encode_batch(docs, max_nodes=MAX_NODES)
    ids = reg.schema_ids(endpoints)
    check(bool((ids >= 0).all()), "every gateway endpoint must ride the linked tape")
    bv = reg.batch_validator()
    check(bv.use_pallas, "the registry's launch must use the Pallas kernels")
    ref_bv = BatchValidator(reg.linked_tape(), max_depth=reg.max_depth, use_pallas=False)
    with phase("launch_pallas"):
        valid, decided, _ = bv.validate_ex(table, ids)
    with phase("launch_jnp"):
        ref_valid, ref_decided, _ = ref_bv.validate_ex(table, ids)
    check(np.array_equal(valid, ref_valid), "Pallas and jnp launches disagree on valid")
    check(np.array_equal(decided, ref_decided), "Pallas and jnp launches disagree on decided")
    check(
        bool((valid[decided] == expected[decided]).all()),
        "a decided launch verdict differs from NaiveValidator",
    )
    out["launch_decided_share"] = float(decided.mean())
    with phase("kernel_check"):
        check(launch_has_kernel(bv, table, ids), "the compiled launch holds no tpu_custom_call")

    # -- explain the rejects, directly -----------------------------------------
    invalid = np.flatnonzero(decided & ~valid)
    check(invalid.size > 0, "no decided-invalid rows to explain")
    with phase("explain"):
        sites = bv.explain_batch(
            table.take(invalid), ids[invalid], docs=[docs[i] for i in invalid]
        )
    for site, i in zip(sites, invalid):
        check(site is not None, f"explain_batch found no failure in invalid row {i}")
        ok, trace = reg.get(endpoints[i]).validator.explain(docs[i])
        check(not ok, f"the sequential trace accepts invalid row {i}")
        check(
            site.schema_path in {p for p, _ in trace},
            f"row {i}: batched site {site.schema_path} is not blamed by the sequential trace",
        )
    out["explained"] = int(invalid.size)

    # -- decode a handful of the admitted prompts through ServeEngine.step ------
    finite = []

    def checked(fn):
        def call(*args, **kw):
            logits, cache = fn(*args, **kw)
            finite.append(bool(jnp.isfinite(logits).all()))
            return logits, cache

        return call

    engine.model.prefill = checked(engine.model.prefill)
    engine._decode = checked(engine._decode)
    handful = engine.queue[:DECODE_REQUESTS]
    check(len(handful) == DECODE_REQUESTS, "too few admitted prompt-bearing requests")
    engine.queue[:] = handful
    for slot in handful:
        slot.max_tokens = min(slot.max_tokens, DECODE_TOKENS)
    with phase("decode"):
        engine.run_until_drained(max_steps=4 * DECODE_REQUESTS * DECODE_TOKENS)
    check(finite and all(finite), "non-finite logits in prefill or decode")
    check(
        all(s.request_id in engine.results for s in handful),
        "a decoded request did not complete",
    )
    out["decode_requests"] = len(handful)
    out["decode_tokens"] = sum(len(s.generated) for s in handful)
    out["decode_steps"] = engine.stats.decode_steps

    out["wall_s"] = phase.wall_s
    out["compile_s"] = phase.compile_s
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and traffic seed")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})")
    print(f"device {dev.device_kind} x{len(devices)}", flush=True)

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile_cache {enable_compile_cache()}", flush=True)
    summary = run(get_config(ARCH), seed=args.seed)
    for key, value in summary.items():
        print(f"{key} {json.dumps(value)}")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
            }
        )
    )


if __name__ == "__main__":
    main()
