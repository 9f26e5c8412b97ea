"""The documents source of the Table 3 estate: one endpoint per dataset
that the configuration serves, schemas regenerated by
``bench/traffic/corpus.py`` at the configuration's fixed schema seed and
the dataset's Table 3 row, documents drawn from the run's seed."""

from __future__ import annotations

import random
from typing import Any, Dict, List, Sequence

from bench.lib.traffic import break_keyword
from bench.traffic import corpus


class Table3:
    def __init__(self, config: Dict[str, Any]):
        self.samplers = {}
        self.schemas: Dict[str, Any] = {}
        for name, _n_docs, schema_kb, avg_doc_bytes, row in config["datasets"]:
            bp, _ = corpus.build_schema(
                name,
                schema_kb,
                avg_doc_bytes,
                seed=corpus.schema_seed(int(config["schema_seed"]), int(row)),
            )
            self.schemas[name] = bp.schema
            self.samplers[name] = bp.sample_doc

    def draw(self, endpoints: Sequence[str], rng: random.Random) -> List[Any]:
        """One valid document for each of ``endpoints``."""
        return [self.samplers[ep](random.Random(rng.randint(0, 2**31))) for ep in endpoints]

    def break_one(self, endpoint: str, doc: Any, rng: random.Random) -> bool:
        return break_keyword(self.schemas[endpoint], doc, rng)


def build(config: Dict[str, Any]) -> Table3:
    return Table3(config)
