"""``correct``'s control and its reference.

The control (the reference with the documents' numbers in bfloat16, put
in the program's place) has to come out not correct on every cell's
traffic, at a size a test run can hold and on three seeds.  The
benchmark's copy of the reference agrees with the program's
``NaiveValidator`` on the same traffic."""

import pytest

from bench.control import control
from bench.lib import check, spec
from bench.lib import traffic as traffic_lib

SEEDS = (5, 2**31 + 1, 2**33 + 7)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", ["schemastore.taped", "schemastore.full"])
def test_control_is_not_correct(workload, seed, monkeypatch):
    # a pool of one submission, or one second of the open loop
    real = spec.traffic

    def small(name, bench=spec.BENCH):
        tr = real(name, bench)
        return dict(tr, pool=1) if tr["loop"] == "closed" else tr

    monkeypatch.setattr(spec, "traffic", small)
    reading = control(workload, seed, seconds=1.0)
    assert reading["mismatches"] > reading["limit"] == 0


def test_control_is_not_correct_on_the_full_estate(monkeypatch):
    real = spec.traffic
    monkeypatch.setattr(spec, "traffic", lambda name, bench=spec.BENCH: dict(real(name, bench), pool=1))
    for seed in SEEDS:
        assert control("schemastore.full", seed, seconds=1.0)["mismatches"] > 0


@pytest.mark.parametrize("workload", ["schemastore.taped", "schemastore.full"])
def test_reference_copy_agrees_with_the_program(workload):
    import json

    from repro.core import NaiveValidator

    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell["config"])
    source = spec.documents(config["documents"]).build(config)
    tr = spec.traffic(cell["traffic"])
    reqs = traffic_lib.generate(source, dict(tr, malformed_share=0.0), n=400, seed=11, seconds=1.0)
    ref = check.Reference(source.schemas, config["guard"])
    program = {ep: NaiveValidator(schema) for ep, schema in source.schemas.items()}
    verdicts = set()
    for ep, text in zip(reqs.endpoints, reqs.texts):
        want = ref.expect(ep, text)
        verdicts.add(want)
        assert want == (check.VALID if program[ep].is_valid(json.loads(text)) else check.INVALID)
    assert verdicts == {check.VALID, check.INVALID}
