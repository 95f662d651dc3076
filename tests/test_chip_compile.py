"""Ask the TPU compiler, without a TPU: the served path's Pallas kernels at
real widths, and one whole plan, compiled for a DESCRIBED v5e chip.

Nothing runs — a compile that passes is not a chip run and says nothing
about results or times.  What it catches is what interpret mode cannot:
a kernel Mosaic refuses (misaligned slice, too much VMEM), a plan that
does not fit, a program whose kernel silently fell out (no
``tpu_custom_call``).

The topology is described inside a module fixture (one process may load
libtpu at a time — see /opt/skills/guides/on-chip-measurement §2), the
persistent compilation cache is off around the compiles (a described-chip
executable cannot be read back), and ``pallas_kernels._interpret`` is
patched HERE: the program takes its CPU branch off-TPU and gets no option
to do otherwise.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from kolibrie_tpu.ops import pallas_kernels as pk  # noqa: E402

K128 = 131072
M1 = 1 << 20


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the kernels' interpret switch forced
    off and the persistent cache disabled."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    interpret_was = pk._interpret
    pk._interpret = lambda: False
    # traces made by earlier modules baked interpret=True into the jitted
    # entry points' caches; ours must not leak the other way either
    jax.clear_caches()
    try:
        yield topo
    finally:
        pk._interpret = interpret_was
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """SingleDeviceSharding on one chip of the described host."""
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *args, lead=(), want_kernel=True, **static):
    """Lower + compile jitted ``fn`` for the described chip from shapes
    alone (``lead`` = leading static positionals); asserts the Mosaic
    kernel is in the program."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args,
    )
    lowered = fn.lower(*lead, *shapes, **static)
    if want_kernel:
        assert "tpu_custom_call" in lowered.as_text(), "Pallas kernel missing"
    return lowered.compile()


def _u32(n):
    return jax.ShapeDtypeStruct((n,), jnp.uint32)


def _i32(n):
    return jax.ShapeDtypeStruct((n,), jnp.int32)


def _f32(n):
    return jax.ShapeDtypeStruct((n,), jnp.float32)


def _bool(n):
    return jax.ShapeDtypeStruct((n,), jnp.bool_)


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("x64", [False, True])
def test_merge_join_128k(one_chip, x64):
    with jax.enable_x64(x64):
        _compile(pk.merge_join_indices, one_chip, _u32(K128), _u32(K128),
                 cap=K128)


def test_merge_join_blocked_searches_64k(one_chip):
    """With validity masks, as ``_plan_body`` calls it: the run-bound
    searches are a loop of blocks under a traced trip count (ISSUE 39)."""
    n = 65536

    def masked(lkey, rkey, lvalid, rvalid):
        return pk.merge_join_indices(lkey, rkey, n, lvalid, rvalid)

    with jax.enable_x64(True):
        compiled = _compile(jax.jit(masked), one_chip, _u32(n), _u32(4 * n),
                            _bool(n), _bool(4 * n))
    assert "while" in compiled.as_text()


def test_merge_join_single_launch_limit(one_chip):
    n = pk._PALLAS_MAX_LEFT_ROWS
    _compile(pk.merge_join_indices, one_chip, _u32(n), _u32(n), cap=n)


def test_merge_join_chunked_1m(one_chip):
    with jax.enable_x64(True):
        _compile(pk.merge_join_indices, one_chip, _u32(M1), _u32(M1),
                 cap=M1, chunk_out=pk._CHUNK_OUT)


def test_ranked_merge_join(one_chip):
    u64 = jax.ShapeDtypeStruct((K128,), jnp.uint64)
    with jax.enable_x64(True):
        _compile(pk.ranked_merge_join_indices, one_chip, u64, u64, cap=K128)


def test_lex_probe_select(one_chip):
    acc = tuple((_i32(M1), _u32(M1), _u32(M1), _u32(M1), _u32(M1))
                for _ in range(2))
    _compile(jax.jit(pk.lex_probe_select), one_chip,
             _i32(M1), _i32(M1), _bool(M1), acc)


def test_lex_probe_validate(one_chip):
    acc = tuple(tuple(_i32(M1) for _ in range(7)) for _ in range(2))
    _compile(jax.jit(pk.lex_probe_validate), one_chip,
             _bool(M1), _bool(M1), _i32(M1), acc)


def test_filter_mask(one_chip):
    consts = jax.ShapeDtypeStruct((8,), jnp.int32)
    _compile(pk._filter_mask_jit, one_chip, consts,
             _u32(4 * M1), _u32(4 * M1), _u32(4 * M1))


def test_tag_combine(one_chip):
    _compile(pk.tag_combine, one_chip, _f32(M1), _f32(M1), op="noisy_or")


# --------------------------------------------------------------- whole plan


def test_segment_aggregate_at_the_bsbm_cells_widths(one_chip):
    """The aggregation that ends BI Q5's dispatch at 10 M triples (ISSUE 42):
    four columns 2,097,152 slots wide, COUNT(?review) GROUP BY ?country
    ?product into 1,048,576 group slots: one multi-operand sort and the
    scatter-reductions, no kernel of ours."""
    from kolibrie_tpu.optimizer.device_engine import _segment_aggregate

    n, cap = 1 << 21, 1 << 20
    f64 = jax.ShapeDtypeStruct((1,), jnp.float64)
    with jax.enable_x64(True):
        compiled = _compile(
            _segment_aggregate, one_chip, (_u32(n),) * 4, _bool(n), f64,
            want_kernel=False, gpos=(0, 1), funcs=("COUNT",), apos=(2,),
            distincts=(False,), cap=cap)
    text = compiled.as_text()
    assert "sort" in text and "scatter" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30  # a sixteenth of the chip


@pytest.fixture(scope="module")
def lubm_db():
    """A small real device-mode store; its argument tree gives the whole
    plan its shapes."""
    from examples import lubm
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    s, p, o = lubm.generate_fast(4, db.dictionary)
    db.store.add_batch(s, p, o)
    db.execution_mode = "device"
    return db


def _lower_bgp(db, sparql):
    """The LoweredPlan the batched dispatch builds for a plain SELECT
    (``executor.execute_queries_batched``), without executing anything."""
    from kolibrie_tpu.optimizer.device_engine import lower_plan
    from kolibrie_tpu.query import executor as ex

    db.register_prefixes_from_query(sparql)
    ent, _slot = ex._plan_cache_entry(db, sparql)
    _q, w = ex._batchable_select(db, ent["cq"])
    resolved = [ex.resolve_pattern(db, p) for p in w.patterns]
    logical = ex.build_logical_plan(resolved, list(w.filters), [], None)
    plan = ex.Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    return lower_plan(db, plan)


def test_whole_plan_lubm_q9(one_chip, lubm_db):
    from examples import lubm
    from kolibrie_tpu.optimizer import device_engine as de

    low = _lower_bgp(lubm_db, lubm.LUBM_Q9)
    low.build()
    low.calibrate_host()
    spec, args = low.build()
    with jax.enable_x64(True):
        compiled = _compile(de._run_plan, one_chip, *args, lead=(spec, True))
    # the third level is 8,192 wide over 16,384-row orders, so its ``live``
    # range searches take the sorted form (``ops/wcoj.py`` ``range_search_form``):
    # the chip's compiler has seen both forms in one plan
    text = compiled.as_text()
    assert low._join_caps[-1] >= 8192 and " sort(" in text
    # every accessor names a predicate, so every search runs over the window
    # its constants select (ISSUE 48): the slices are in the program
    windows = [a.window for lv in spec.root.levels for a in lv.accessors]
    assert all(0 < w < low._seg_rows[0][0] for w in windows)
    assert " dynamic-slice(" in text
    # a level maps its slots to rows by one scatter and a prefix count
    # (ISSUE 51): no instruction traced under a level's ``expand`` scope is a
    # loop or a sort in the chip's program, while ``probe`` and ``live``
    # keep their searches' loops
    levels = len(spec.root.levels)
    scoped = {scope: [ln for ln in text.splitlines() if f"/{scope}/" in ln]
              for scope in ("expand", "probe", "live")}
    assert len({ln.split("/expand/")[0].rsplit("/", 1)[-1]
                for ln in scoped["expand"]}) == levels  # wcoj0.L0 .. L2
    for scope, lines in scoped.items():
        loops = [ln for ln in lines if " while(" in ln]
        assert bool(loops) == (scope != "expand"), (scope, loops[:2])
    assert not [ln for ln in scoped["expand"] if " sort(" in ln]


def test_a_merge_join_plan_holds_no_sort(one_chip, lubm_db):
    """ISSUE 40: a template of scans and Pallas merge joins compiles without
    a ``sort``: the prepass compacts its matched rows by a search and a
    scan's two-tier branch gathers its rows (a sort compiled for 17-29 s on
    the chip's host, one a join and two a column of a wide scan's scatter:
    PERF.md section 6, PR 40).  The Mosaic kernel and both branches of the
    scans' conditional are in the program."""
    from examples import lubm
    from kolibrie_tpu.optimizer import device_engine as de

    low = _lower_bgp(
        lubm_db,
        f"PREFIX ub: <{lubm.UB}>\n"
        "SELECT ?x ?y ?c WHERE { "
        "?x ub:memberOf <http://www.Department0.University0.edu> . "
        "?x ub:advisor ?y . ?y ub:teacherOf ?c }")
    spec, args = low.build()
    joins = list(de._spec_nodes(spec.root, de.JoinSpec))
    assert len(joins) == 2 and all(j.rsorted for j in joins)
    with jax.enable_x64(True):
        compiled = _compile(de._run_plan, one_chip, *args, lead=(spec, True))
    text = compiled.as_text()
    assert " conditional(" in text and " sort(" not in text


def test_whole_plan_batch_slot_class_8(one_chip, lubm_db):
    """The one-chip group program (``_run_plan_batch``: the live-member loop
    whose body is the solo plan body) for a class of 8: the Pallas
    merge-join kernels stay in it, inside the ``while``."""
    from examples import lubm
    from kolibrie_tpu.optimizer import device_engine as de

    variants = [
        f"PREFIX ub: <{lubm.UB}>\n"
        "SELECT ?x ?y ?c WHERE { "
        f"?x ub:memberOf <http://www.Department{k}.University{k % 4}.edu> . "
        "?x ub:advisor ?y . ?y ub:teacherOf ?c }"
        for k in range(8)
    ]
    lows = [_lower_bgp(lubm_db, v) for v in variants]
    built = [lp.build() for lp in lows]
    spec0, (order_arrays, _sc, tiers, masks, values, numf, quoted, _pp) = built[0]
    assert all(spec == spec0 for spec, _ in built)
    with jax.enable_x64(True):
        scal = np.stack([np.asarray(lp._scan_ranges_np) for lp in lows])
        params_b = (
            np.stack([np.asarray(lp.u_params or [0], np.uint32) for lp in lows]),
            np.stack([np.asarray(lp.f_params or [0.0], np.float64) for lp in lows]),
        )
        compiled = _compile(
            de._run_plan_batch, one_chip, order_arrays, scal, np.int32(8),
            tiers, masks, values, numf, quoted, params_b, lead=(spec0, True))
    assert "while" in compiled.as_text()


def _mesh_program(topo, config_name, template, domain, subj, obj, caps=None):
    """One template's mesh serving program (``sharded_serving._batched_body``:
    the live-member loop with its ``all_to_all`` inside) lowered for the four
    chips of the described host with shards ``subj`` and ``obj`` slots wide
    (base blocks + delta blocks), each mirror's sorted keys and rows beside
    it in the state, slot class 8.  The template's lowering, plan and
    capacities come from LUBM(1) of the configuration's generator on CPU
    devices (a department's or university's counts do not grow with the
    store; ``caps`` where the cell's hottest key at full scale gives wider
    ones than LUBM(1)'s).  ``(executor, LUBM(1)'s capacities, lowered)``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.harness import data as bench_files
    from kolibrie_tpu.parallel import make_mesh
    from kolibrie_tpu.parallel import sharded_serving as ss
    from kolibrie_tpu.query.executor import _plan_cache_entry
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    config = bench_files.read_json("configs", config_name + ".json")
    data = bench_files.load_module("generators", config["generator"]).generate(
        config, 7, 1)
    db = SparqlDatabase()
    ids = np.array([db.dictionary.encode(t[1:-1] if t.startswith("<") else t)
                    for t in data["terms"]], dtype=np.uint32)
    db.store.add_batch(ids[data["s"]], ids[data["p"]], ids[data["o"]])
    db.execution_mode = "host"
    sh = ss.attach_sharded(db, make_mesh(4))
    sh.refresh()
    text = bench_files.template_text(template).replace(
        "@%s@" % domain, data["domains"][domain][0])
    db.register_prefixes_from_query(text)
    fp = _plan_cache_entry(db, text)[0]["fp"]
    with sh.lock:
        group = sh._build_group(fp, [(0, text)])
    ex = group["execs"][0]
    assert ex.plan_source == "counted"
    mesh = Mesh(np.array(topo.devices).reshape(4), (sh.axis,))
    fn = ss._get_batched_fn(
        mesh, group["premises"], ex.seed, ex.steps, ex.filters, ex.out_vars,
        len(group["masks"]), *(caps or group["caps"]), ss._slot_class(1),
    )
    rows = NamedSharding(mesh, P(sh.axis, None))
    everywhere = NamedSharding(mesh, P())

    def shape(a, sharding, dims=None):
        return jax.ShapeDtypeStruct(dims or a.shape, a.dtype, sharding=sharding)

    state = (*sh.view.by_subj, sh.view.by_subj_valid,
             *sh.view.by_obj, sh.view.by_obj_valid,
             *sh._subj_sorted, *sh._obj_sorted)
    dims = [(4, subj)] * 4 + [(4, obj)] * 4 + [(4, subj)] * 2 + [(4, obj)] * 2
    with jax.enable_x64(True):
        lowered = fn.lower(
            tuple(shape(a, rows, d) for a, d in zip(state, dims)),
            tuple(shape(m, everywhere) for m in group["masks"]),
            shape(group["params"], everywhere),
            jax.ShapeDtypeStruct((), np.int32, sharding=everywhere),
        )
    return ex, group["caps"], lowered


def _compiled_for_four_chips(lowered):
    with jax.enable_x64(True):
        compiled = lowered.compile()
    text = compiled.as_text()
    assert " all-to-all(" in text and " while(" in text
    per_chip = compiled.memory_analysis()
    assert per_chip.output_size_in_bytes + per_chip.temp_size_in_bytes < 2**30


def test_mesh_program_lubm_q7_four_chips(topo, mesh8):
    """At the widths the cell ``lubm5.mesh4`` runs: 262,144-slot shards, the
    plan and the capacities the host count gives Q7 (from the professor,
    1,024 · 1,024 · 1,024)."""
    ex, caps, lowered = _mesh_program(
        topo, "lubm-5-mesh4", "lubm_q7", "department",
        262144 + 1024, 262144 + 1024)
    assert caps == (1024, 1024, 1024)
    assert ex.premises[ex.seed].consts[0] is not None  # the professor
    assert any(kv != "x" for (_j, kv, _kp, _e) in ex.steps)  # an exchange
    _compiled_for_four_chips(lowered)


def test_mesh_program_lubm_q8_at_the_widths_of_lubm50_mesh4(topo, mesh8):
    """At the widths the cell ``lubm50.mesh4`` runs (ISSUE 50): 2,097,152-slot
    subject shards and 4,194,304-slot object shards, the capacities the host
    count gives the hottest of fifty universities there (65,536 · 2,048 ·
    4,096; LUBM(1)'s one university counts half).  The program sorts no
    mirror and takes no prefix sum over one flat (until PR 50 each was most
    of a 20 s compile), and exchanges between its join steps."""
    _ex, (join_cap, _bucket_cap, out_cap), lowered = _mesh_program(
        topo, "lubm-50-mesh4", "lubm_q8", "university",
        2097152 + 1024, 4194304 + 1024, caps=(65536, 2048, 4096))
    assert 16384 <= join_cap <= 65536 and 1024 <= out_cap < join_cap
    hlo = lowered.as_text()
    for wide in ("2098176", "4195328"):
        assert not any(wide in line for line in hlo.splitlines()
                       if "stablehlo.sort" in line or "cumsum" in line
                       or "reduce_window" in line), wide
    _compiled_for_four_chips(lowered)
