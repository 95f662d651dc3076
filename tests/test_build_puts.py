"""A dispatch sends up what changed since the last one (ISSUE 49): the build
of a device dispatch makes no device array that the request before it already
made, and a request's four small vectors (scan ranges, tiers, the two
parameter vectors) travel with the jit call as numpy arguments, so

- on a warmed template a dispatch's ``build_put_counters()`` do not grow,
  solo or in a group of any size; an order's first use, a delta's new epoch
  and a grown table still count their uploads;
- the answers are the numpy twin's request after request while the store and
  the dictionary move under the template, and ``f`` stays float64;
- the executables are the ones the operands' earlier form (a ``jnp.asarray``
  a vector) built: nothing is traced, lowered or compiled again, and the
  persistent cache's key and the journal's identity are the same.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from test_delta_tier import (
    EX, PREFIX, TEMPLATES, THRESHOLD, _edge, _graph_db, _lower, _rows, _tiers,
    _write_a_little)

from kolibrie_tpu.obs import export as obs_export
from kolibrie_tpu.optimizer import device_engine as de
from kolibrie_tpu.query.sparql_database import SparqlDatabase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KINDS = ("transfer", "compute")


def _metric() -> dict:
    out = dict.fromkeys(KINDS, 0.0)
    for line in obs_export.render_prometheus().splitlines():
        if line.startswith("kolibrie_device_build_puts_total{"):
            what = line.split('what="')[1].split('"')[0]
            out[what] = float(line.rpartition(" ")[2])
    return out


def _puts(run):
    """``(what run() returned, the build audit's growth over it)``; the
    ``/metrics`` family grows by the same."""
    a0, m0 = de.build_put_counters(), _metric()
    out = run()
    a1, m1 = de.build_put_counters(), _metric()
    grew = {k: a1[k] - a0[k] for k in KINDS}
    assert grew == {k: m1[k] - m0[k] for k in KINDS}
    return out, grew


NOTHING = {"transfer": 0, "compute": 0}


def _variants(n, first="p1", second="p2"):
    return [PREFIX + f"SELECT ?b ?c WHERE {{ ex:n{k} ex:{first} ?b . ?b ex:{second} ?c }}"
            for k in range(n)]


def _dispatch(db, texts):
    """One dispatch of ``texts``: a lone request's ``execute()`` or the
    group's ``execute_plan_batch``; every member's rows checked against its
    numpy twin."""
    lows = [_lower(db, t) for t in texts]
    if len(lows) == 1:
        tables, grew = _puts(lambda: [lows[0].execute()])
    else:
        tables, grew = _puts(lambda: de.execute_plan_batch(lows))
    for low, table in zip(lows, tables):
        assert _rows(table) == _rows(low.host_execute()[0])
    return lows, grew


# ------------------------------------------- (1) what a warmed dispatch builds


@pytest.mark.parametrize("size", [1, 2, 8])
def test_a_warmed_dispatch_builds_no_device_array(size):
    db = _graph_db()
    texts = _variants(size)
    # an order's first use uploads its base and its delta: a transfer each;
    # the placeholders (no number compared, no quoted triple named) are made
    # once a process, wherever this test runs
    lows, first = _dispatch(db, texts)
    orders = len(lows[0].order_names)
    assert orders >= 2 and first["transfer"] == 2 * orders
    assert first["compute"] in (0, 1, 2)
    assert set(de._DEVICE_ZEROS) >= {np.float32, np.uint32}
    # warmed: the second dispatch and the third send nothing up of their own
    for shift in (1, 2):
        again = texts[shift:] + texts[:shift]
        _lows, grew = _dispatch(db, again)
        assert grew == NOTHING
    # a write under the threshold is a new delta epoch: the deltas go up
    # again, the bases stay
    _write_a_little(db)
    _lows, grew = _dispatch(db, texts)
    assert grew == {"transfer": orders, "compute": 0}
    _lows, grew = _dispatch(db, texts)
    assert grew == NOTHING


def test_the_operands_of_a_build_are_the_host_vectors_and_the_kept_constants():
    """The tuple's layout is the one ``optimizer/mqo.py`` and
    ``optimizer/plan_interp.py`` read by position; the four vectors are the
    numpy arrays the build holds, in the dtypes the entry points have always
    seen; a placeholder is the process's one array of its kind."""
    import jax

    db = _graph_db()
    low = _lower(db, _variants(1)[0])
    spec, args = low.build()
    orders, scalars, tiers, masks, values, numf, quoted, (u, f) = args
    assert len(orders) == len(spec.orders) and masks == () and values == ()
    for host, dtype, shape in (
            (scalars, np.int32, (len(low.scan_descs), 4)),
            (tiers, np.int32, (len(orders),)),
            (u, np.uint32, (max(len(low.u_params), 1),)),
            (f, np.float64, (max(len(low.f_params), 1),))):
        assert type(host) is np.ndarray
        assert (host.dtype, host.shape) == (np.dtype(dtype), shape)
    assert scalars is low._scan_ranges_np and tiers is low._tiers_np
    assert isinstance(numf, jax.Array) and (numf.dtype, numf.shape) == (
        np.dtype(np.float32), (1,))
    assert len(quoted) == 4 and all(
        isinstance(q, jax.Array) and (q.dtype, q.shape) == (np.dtype(np.uint32), (1,))
        for q in quoted)
    _spec, args2 = _lower(db, _variants(2)[1]).build()
    assert args2[5] is numf and all(a is b for a, b in zip(args2[6], quoted))
    # what the jit sees: int32, int32, uint32, float64, none of them weak
    with jax.enable_x64(True):
        avals = jax.make_jaxpr(
            lambda *a: de._run_plan(spec, False, *a))(*args).in_avals
    flat = jax.tree_util.tree_leaves(args)
    small = [(str(a.dtype), a.weak_type) for a, x in zip(avals, flat)
             if type(x) is np.ndarray]
    assert small == [("int32", False), ("int32", False), ("uint32", False),
                     ("float64", False)]


# ---------------------------------- (2a) the store moves under the template


@pytest.mark.parametrize("name, size", [
    ("join2", 1), ("rsorted", 1), ("triangle", 1), ("rsorted", 2), ("rsorted", 8)])
def test_tiers_and_ranges_are_the_stores_of_that_moment(name, size, monkeypatch):
    """Request after request across an insert and a delete (the delta tier
    of every order turns from empty to held) and the writes that fold it
    back into a new base: the rows are the twin's each time, the scans take
    the branch the store's delta selects, a write compiles nothing, and a build
    uploads what changed and nothing else."""
    monkeypatch.setenv("KOLIBRIE_WCOJ", "force" if name == "triangle" else "off")
    db = _graph_db()
    if size == 1:
        texts = [PREFIX + TEMPLATES[name][0]]
    else:
        texts = _variants(size)

    def request(tier):
        t0 = _tiers()
        lows, grew = _dispatch(db, texts)
        t1 = _tiers()
        sites = size * len(lows[0]._tier_sites)
        took = (t1[0] - t0[0], t1[1] - t0[1])
        # a capacity retry is one more dispatch of the same sites
        assert took[tier] >= sites and took[tier] % sites == 0
        assert took[1 - tier] == 0
        held = [int(t) for t in lows[0]._tiers_np]
        assert all(t == 0 for t in held) if tier == 0 else all(t > 0 for t in held)
        return lows, grew

    lows, _first = request(0)
    assert TEMPLATES[name][1](lows[0].root) or size > 1
    orders = len(lows[0].order_names)
    request(0)
    compiled = dict(de.device_compile_stats())
    _write_a_little(db)
    _lows, grew = request(1)
    assert grew == {"transfer": orders, "compute": 0}
    _lows, grew = request(1)
    assert grew == NOTHING
    # the write flipped a scalar of ``tiers``: the same executable
    assert dict(de.device_compile_stats()) == compiled
    bv = db.store.base_version
    for k in range(THRESHOLD // 8 + 1):
        if db.store.base_version != bv:
            break
        db.parse_ntriples("\n".join(
            _edge(1000 + 8 * k + i, "p1", 2000 + 8 * k + i) for i in range(8)))
    assert db.store.base_version != bv
    _lows, grew = request(0)
    assert grew == {"transfer": 2 * orders, "compute": 0}  # new bases, new deltas
    _lows, grew = request(0)
    assert grew == NOTHING


# ----------------------------- (2b) the dictionary grows under the template


NAMES = ("alice", "albert", "bob", "carol", "malcolm", "dave")
BIG = 16_777_216  # 2**24: float32 holds it and not the odd number after it


def _staff_lines(lo, hi):
    return "\n".join(
        f'<{EX}e{i}> <{EX}name> "{NAMES[i % 6]}{i}" .\n'
        f'<{EX}e{i}> <{EX}salary> "{BIG - 6 + i}" .\n'
        f'<{EX}e{i}> <{EX}dept> <{EX}d{i % 3}> .'
        for i in range(lo, hi))


def _staff_db():
    db = SparqlDatabase()
    db.parse_ntriples(_staff_lines(0, 40))
    db.execution_mode = "device"
    return db


FILTERS = {
    # name: (query, uploads a warmed request's build still makes)
    "numeric": ("SELECT ?e ?s WHERE { ?e ex:dept ex:d1 . ?e ex:salary ?s "
                f"FILTER(?s >= {BIG + 1}) }}", 0),
    # a string filter's two masks (dictionary, quoted triples) are computed
    # at lowering and uploaded a request: left as they were (docs/COMPILE_CACHE.md)
    "regex": ('SELECT ?e ?n WHERE { ?e ex:dept ex:d1 . ?e ex:name ?n '
              'FILTER(REGEX(?n, "^al")) }', 2),
    "contains": ('SELECT ?e ?n WHERE { ?e ex:dept ex:d1 . ?e ex:name ?n '
                 'FILTER(CONTAINS(?n, "al")) }', 2),
}


@pytest.mark.parametrize("name", sorted(FILTERS))
def test_a_grown_dictionary_reaches_the_next_request(name):
    """The numeric table and the string masks are per dictionary id: new
    ids between two requests of a template (new literals, some of which
    pass the filter) are in the next request's table and masks."""
    db = _staff_db()
    text = PREFIX + FILTERS[name][0]
    warmed_uploads = FILTERS[name][1]
    lows, _first = _dispatch(db, [text])
    assert lows[0].need_numf == (name == "numeric")
    assert len(lows[0].mask_arrays) == warmed_uploads
    before = _rows(lows[0].host_execute()[0])
    _lows, grew = _dispatch(db, [text])
    assert grew == {"transfer": warmed_uploads, "compute": 0}
    ids, bv = len(db.dictionary.id_to_str), db.store.base_version
    db.parse_ntriples(_staff_lines(40, 52))
    lows, grew = _dispatch(db, [text])
    assert len(db.dictionary.id_to_str) > ids
    after = _rows(lows[0].host_execute()[0])
    assert len(after) > len(before) and set(before) < set(after)
    # its orders' deltas (their bases too where the write was folded into a
    # new base), and the numeric table once more
    segments = len(lows[0].order_names) * (1 + (db.store.base_version != bv))
    assert grew == {"transfer": segments + max(warmed_uploads, 1), "compute": 0}
    _lows, grew = _dispatch(db, [text])
    assert grew == {"transfer": warmed_uploads, "compute": 0}


# ------------------------------------------------- (2c) ``f`` stays float64


@pytest.mark.parametrize("size", [1, 2])
def test_a_comparand_float32_cannot_hold_is_compared_exactly(size):
    """``?s >= 16777217``: float32 rounds the constant to 16777216 and would
    let the row of salary 16777216 through."""
    db = _staff_db()
    assert np.float32(BIG + 1) == np.float32(BIG)
    texts = [PREFIX + "SELECT ?e ?s WHERE { ?e ex:salary ?s "
             f"FILTER(?s >= {BIG + 1 + 2 * k}) }}" for k in range(size)]
    lows, _grew = _dispatch(db, texts)
    for k, low in enumerate(lows):
        assert low.f_params == [float(BIG + 1 + 2 * k)]
        _spec, args = low.build()
        assert args[7][1].dtype == np.float64
        salaries = sorted(
            float(db.decode_term(int(s)).strip('"').split('"')[0])
            for s in low.execute()["s"])
        assert salaries == [float(v) for v in range(BIG + 1 + 2 * k, BIG + 34)]


# --------------------------------------- (3) the executables are the same ones


def _earlier_form(args):
    """The operands as builds made them before: a ``jnp.asarray`` a vector
    and fresh placeholders."""
    import jax
    import jax.numpy as jnp

    orders, scalars, tiers, masks, values, _numf, _quoted, (u, f) = args
    with jax.enable_x64(True):
        return (
            orders, jnp.asarray(scalars), jnp.asarray(tiers), masks, values,
            jnp.zeros(1, dtype=jnp.float32),
            tuple(jnp.zeros(1, dtype=jnp.uint32) for _ in range(4)),
            (jnp.asarray(u), jnp.asarray(f, dtype=jnp.float64)))


def _earlier_batch_form(lows, slots):
    import jax
    import jax.numpy as jnp

    def rows(of, dtype):
        mat = np.zeros((slots, *np.shape(of(lows[0]))), dtype=dtype)
        mat[: len(lows)] = [of(lp) for lp in lows]
        return mat

    built = [lp.build() for lp in lows]
    spec, args = built[0]
    orders, _sc, tiers, masks, values, numf, quoted, _pp = _earlier_form(args)
    with jax.enable_x64(True):
        return spec, (
            orders, jnp.asarray(rows(lambda lp: lp._scan_ranges_np, np.int32)),
            np.int32(len(lows)), tiers, masks, values, numf, quoted,
            (jnp.asarray(rows(lambda lp: lp.u_params or [0], np.uint32)),
             jnp.asarray(rows(lambda lp: lp.f_params or [0.0], np.float64),
                         dtype=jnp.float64)))


@pytest.mark.parametrize("size", [1, 2])
def test_a_template_warmed_on_the_earlier_operand_form_is_not_built_again(size):
    """Same avals, same pytree: after a call with device arrays for the
    vectors, the dispatch as it is now traces, lowers and compiles nothing
    (no first-sight record), and the journal's identity of the call is the
    one the earlier form had.  ``device_compile_stats()`` reads the size of
    the jit's call-signature cache, which tells a numpy argument from a
    device array: it grows by that one signature the first time and by
    nothing after, with no executable behind it (a process dispatches in one
    form only, so ``compiles_in_window`` never sees it)."""
    import jax

    from kolibrie_tpu.query import compile_cache as cc

    db = _graph_db(seed=29)
    jax.clear_caches()  # no test before has warmed the template in either form
    texts = _variants(size)
    lows = [_lower(db, t) for t in texts]
    entry = "run_plan" if size == 1 else "run_plan_batch"
    with jax.enable_x64(True):
        if size == 1:
            spec, args = lows[0].build()
            earlier = _earlier_form(args)
            cc.call(de._run_plan, spec, False, *earlier)
        else:
            spec, earlier = _earlier_batch_form(lows, 8)
            args = None
            cc.call(de._run_plan_batch, spec, False, *earlier)
    assert cc.last_sight() is not None  # that was this shape's first sight
    records = len(cc.records())
    stats = de.device_compile_stats()[entry]
    if size == 1:
        fun = "_run_plan"
        assert cc._identity(entry, fun, spec, args) == cc._identity(
            entry, fun, spec, earlier)
    for attempt in range(2):
        _dispatch(db, texts)
        assert len([r for r in cc.records()[records:] if r["entry"] == entry]) == 0
        assert de.device_compile_stats()[entry] == stats + 1


_PROC = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r}); sys.path.insert(0, os.path.join({repo!r}, "tests"))
from kolibrie_tpu.query import compile_cache as cc
cc.enable(explicit_dir={root!r})
import jax
import kolibrie_tpu.optimizer.device_engine as de
from test_build_puts import _earlier_batch_form, _earlier_form, _variants
from test_delta_tier import _graph_db, _lower

db = _graph_db(seed=31)
out = {{}}
low = _lower(db, _variants(1)[0])
spec, args = low.build()
with jax.enable_x64(True):
    cc.call(de._run_plan, spec, False, *_earlier_form(args))
out["solo_earlier"] = cc.last_sight()
lows = [_lower(db, t) for t in _variants(3)]
spec_b, earlier_b = _earlier_batch_form(lows, 8)
with jax.enable_x64(True):
    cc.call(de._run_plan_batch, spec_b, False, *earlier_b)
out["group_earlier"] = cc.last_sight()
jax.clear_caches()  # what a restart leaves: the directory
seen = len(cc.records())
_lower(db, _variants(1)[0]).execute()
de.execute_plan_batch([_lower(db, t) for t in _variants(3)])
out["now"] = [r for r in cc.records()[seen:]
              if r["entry"] in ("run_plan", "run_plan_batch")]
print(json.dumps(out))
"""


def test_the_persistent_cache_finds_what_the_earlier_form_wrote(tmp_path):
    """A directory written by calls in the earlier operand form serves the
    dispatches as they are now: the same keys, the same identities, every
    outcome a hit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("KOLIBRIE_PLAN_INTERP", "KOLIBRIE_COMPILE_CACHE_DIR",
                 "JAX_COMPILATION_CACHE_DIR", "KOLIBRIE_MQO", "KOLIBRIE_WCOJ"):
        env.pop(name, None)
    script = _PROC.format(repo=REPO, root=str(tmp_path / "cc"))
    done = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=240, env=env, cwd=REPO)
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.splitlines()[-1])
    earlier = {"run_plan": out["solo_earlier"], "run_plan_batch": out["group_earlier"]}
    assert [r["entry"] for r in out["now"]] == ["run_plan", "run_plan_batch"]
    for rec in out["now"]:
        was = earlier[rec["entry"]]
        assert was["outcome"] == "miss_new" and was["write"] == "ok"
        assert rec["outcome"] == "hit"
        assert (rec["key"], rec["identity"]) == (was["key"], was["identity"])
