"""Quickstart: compile a JSON Schema with Blaze and validate documents.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import json
import time

from repro.core import CompilerOptions, NaiveValidator, Validator, compile_schema

SCHEMA = {
    "$schema": "https://json-schema.org/draft/2020-12/schema",
    "type": "object",
    "required": ["firstName", "lastName"],
    "additionalProperties": False,
    "properties": {
        "firstName": {"type": "string", "maxLength": 100},
        "middleName": {"type": "string", "maxLength": 100},
        "lastName": {"type": "string", "maxLength": 100},
        "age": {"type": "integer", "minimum": 0},
        "email": {"type": "string", "pattern": "^[^@]+@"},
        "role": {"enum": ["admin", "editor", "viewer"]},
    },
}

DOCS = [
    {"firstName": "Douglas", "lastName": "Jason", "age": 20},          # valid
    {"firstName": "Ada", "lastName": "L", "role": "admin"},            # valid
    {"firstName": "Bob"},                                              # missing lastName
    {"firstName": "Eve", "lastName": "X", "age": -1},                  # minimum
    {"firstName": "Mallory", "lastName": "Y", "color": "red"},         # closed object
]


def main() -> None:
    # Compile once (schemas change every ~65 days; validation runs per request)
    t0 = time.perf_counter()
    compiled = compile_schema(SCHEMA)
    print(f"compiled {compiled.instruction_count()} instructions "
          f"in {(time.perf_counter()-t0)*1e3:.2f} ms")

    validator = Validator(compiled)
    for doc in DOCS:
        print(f"  {'VALID  ' if validator.is_valid(doc) else 'INVALID'}  {json.dumps(doc)}")

    # Hot loop vs the naive interpreting validator.  Documents are parsed
    # once (the paper computes hashes at parse time, §4.1) -- an API
    # gateway parses each request exactly once anyway.
    from repro.core.doc_model import parse_document

    naive = NaiveValidator(SCHEMA)
    codegen = Validator(compiled, engine="codegen")
    parsed = [parse_document(d) for d in DOCS]
    n = 20_000
    timings = {}
    # the interpreter reads the parsed model, codegen the decoded tree
    for name, fn, docs in [
        ("blaze", lambda d: validator.is_valid(d, parsed=True), parsed),
        ("codegen", codegen.is_valid, DOCS),
    ]:
        t0 = time.perf_counter()
        for _ in range(n // len(DOCS)):
            for doc in docs:
                fn(doc)
        timings[name] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n // len(DOCS)):
        for doc in DOCS:
            naive.is_valid(doc)
    timings["naive"] = time.perf_counter() - t0
    print(f"\nhot loop ({n} validations):")
    for name in ("blaze", "codegen", "naive"):
        rel = timings["naive"] / timings[name]
        print(f"  {name:8s} {timings[name]*1e9/n:8.0f} ns/doc   ({rel:.1f}x vs naive)")


if __name__ == "__main__":
    main()
