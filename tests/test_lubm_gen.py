"""The vectorized LUBM generator must emit EXACTLY the loop generator's
triple set — every LUBM benchmark number rests on this equivalence."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))

from kolibrie_tpu.core.dictionary import Dictionary


def test_generate_fast_equals_loop_generator():
    from lubm import generate, generate_fast

    d = Dictionary()
    s1, p1, o1 = generate(3, d)
    s2, p2, o2 = generate_fast(3, d)  # same dictionary -> same term IDs
    set1 = set(zip(s1.tolist(), p1.tolist(), o1.tolist()))
    set2 = set(zip(s2.tolist(), p2.tolist(), o2.tolist()))
    assert len(s1) == len(s2)
    assert set1 == set2
