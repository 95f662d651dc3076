"""Neurosymbolic ML: declare + train a neural relation with the in-query
syntax, then materialize its predictions with ML.PREDICT.

Mirrors the reference's ``examples/sparql_syntax/ml_train`` path (candle →
JAX MLP here).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from kolibrie_tpu.query.executor import execute_query_volcano  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402

db = SparqlDatabase()
rng = np.random.default_rng(3)
rows = []
for i in range(40):
    hot = i % 2
    t = (80 + rng.normal(0, 3)) if hot else (50 + rng.normal(0, 3))
    rows.append(
        f'ex:m{i} ex:temp "{t:.2f}" ; '
        f'ex:isHot "{"true" if hot else "false"}" .'
    )
db.parse_turtle("@prefix ex: <http://e/> .\n" + "\n".join(rows))

execute_query_volcano(
    """PREFIX ex: <http://e/>
MODEL "hot_model" { ARCH MLP { HIDDEN [8] } OUTPUT BINARY }
NEURAL RELATION ex:predictedHot USING MODEL "hot_model" {
    INPUT { ?m ex:temp ?t . }
    FEATURES { ?t }
}
TRAIN NEURAL RELATION ex:predictedHot {
    DATA { ?m ex:isHot ?hot . }
    LABEL ?hot
    TARGET { ?m ex:predictedHot ?l }
    LOSS bce
    EPOCHS 12
    BATCH_SIZE 8
    LEARNING_RATE 0.1
}""",
    db,
)

execute_query_volcano(
    """PREFIX ex: <http://e/>
    ML.PREDICT(
        MODEL "hot_model",
        INPUT { SELECT ?m ?t WHERE { ?m ex:temp ?t . } },
        OUTPUT ?hot
    )""",
    db,
)
# Binary relations materialize the positive literal for every row, with
# the model's probability as an RDF-star companion fact (reference parity:
# ml_predict_candle.rs:253-258) — consumers read/threshold the annotation.
rows = execute_query_volcano(
    """PREFIX ex: <http://e/>
    PREFIX prob: <http://kolibrie.tpu/prob#>
    SELECT ?m ?p WHERE {
        << ?m ex:predictedHot ?h >> prob:value ?p }
    ORDER BY ?m LIMIT 6""",
    db,
)
print("P(hot) per measurement (sample):")
for row in rows:
    print(row)
