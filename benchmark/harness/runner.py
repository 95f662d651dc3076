"""One run of one cell: set-up, window, comparison, result line.

One process, the only one that touches JAX.  The real server runs in a
thread; the clients are the load generator's, a process of its own.
Everything that belongs to one cell is found by name under ``benchmark/``
(see README.md).
"""

import gc
import json
import multiprocessing
import os
import re
import shutil
import sys
import threading
import time
from collections import Counter

import numpy as np

from . import e2e, layers, loadgen, xplane
from . import data as files
from .client import Client

RING_CAPACITY = 2_000_000  # spans; the program's default is 4096
NO_CHIP_EXIT = 3
ANSWER_TIMEOUT_S = 1200  # longer than any request's deadline
COLLECTIVE = re.compile(r"^(all|collective|reduce[-_]scatter|send|recv)")  # op names


def say(**obj) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def _counters(cl) -> dict:
    """Every counter a reader may ask for, under one flat naming."""
    from kolibrie_tpu.optimizer.device_engine import device_compile_stats
    from kolibrie_tpu.query import compile_cache

    out = {}
    for line in cl.get_text("/metrics").splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            try:
                out["metrics." + key] = float(value)
            except ValueError:
                pass
    for k, v in device_compile_stats().items():
        out["compile." + k] = float(v)
    for k, v in compile_cache.counters().items():
        out["compile_cache." + k] = float(v)
    # the mesh layer's executables, where the program has loaded that layer
    # (a store served from one chip never does)
    mesh_layer = sys.modules.get("kolibrie_tpu.parallel.sharded_serving")
    if mesh_layer is not None:
        for k, v in mesh_layer.sharded_compile_stats().items():
            out["compile.sharded." + k] = float(v)
    return out


def _mesh_proof(check, db, stats, config, requests, counters0, counters1):
    """For a cell on several chips: the mesh, and not device 0 alone, served
    the window (from ``chip_smoke.py``'s ``check_mesh``)."""

    def total(counters, name):
        return sum(v for k, v in counters.items()
                   if k.startswith("metrics." + name))

    def grew(name):
        return total(counters1, name) - total(counters0, name)

    chips = config["chips"]
    sh = db.__dict__.get("_sharded_serving")
    spans_chips = int(sh.mesh.devices.size) if sh is not None else 0
    check("store_attached_to_a_mesh_over_the_chips", spans_chips, chips,
          spans_chips == chips)
    errors = total(counters1, "kolibrie_shard_attach_errors_total")
    check("shard_attach_errors", errors, 0, errors == 0)
    on_devices = 0
    if sh is not None and sh.view is not None:
        view = sh.view
        arrays = [*view.by_subj, view.by_subj_valid, *view.by_obj, view.by_obj_valid]
        on_devices = min(len({s.device.id for s in a.addressable_shards
                              if s.data.size}) for a in arrays)
    check("mirror_arrays_on_distinct_devices", on_devices, chips,
          on_devices == chips)
    fallbacks = grew("kolibrie_shard_fallback_total")
    check("shard_fallbacks_in_window", fallbacks, 0, fallbacks == 0)
    # the rest are stragglers that missed the batcher's window and were
    # served alone from device 0; a store without the mesh serves 0
    served = grew("kolibrie_shard_queries_total")
    least = config["layout"]["served_by_the_mesh_at_least"] * len(requests)
    check("window_requests_served_by_the_mesh", served, f">={least:g}",
          served >= least)
    # not part of ``correct``: a capacity retry leaves the answers exact
    say(phase="mesh", dispatches_in_window=grew("kolibrie_shard_dispatch_total"),
        cap_hits_in_window=grew("kolibrie_shard_exchange_cap_hits_total"),
        stats=stats.get("sharding"))


def run_cell(workload, seed, seconds, trace, t_start, scale=None,
             waive=frozenset(), tamper=None, control=False):
    """Returns ``(result line or None, exit code)``.

    ``scale`` is the rehearsal's override of the configuration's scale: with
    it a run is never ``correct``.  ``waive`` (checks left out of
    ``correct``) and ``tamper`` (``(request index, "drop_row" |
    "alter_value")``: a response altered where the client receives it) are
    for the tests under ``benchmark/tests`` only; nothing on the command line
    or in the environment reaches them.
    """
    bench = files.read_json(os.pardir, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    config = files.read_json("configs", cell["config"] + ".json")
    # the cell's own file holds what BENCHMARK.json has no key for
    os.environ.update(files.read_json("workloads", workload + ".json")["env"])
    if files.REPO_DIR not in sys.path:
        sys.path.insert(0, files.REPO_DIR)
    # the load generator is a process of its own (see loadgen.py); it starts
    # generating while this one starts JAX
    ctx = multiprocessing.get_context("spawn")
    conn, child_end = ctx.Pipe()
    child = ctx.Process(
        target=loadgen.main, daemon=True,
        args=(child_end, config, cell["traffic"], seed, scale, tamper))
    child.start()
    child_end.close()
    try:
        conn.send(("generate",))
        return _serve_and_measure(conn, bench, cell, config, seed, seconds,
                                  trace, t_start, scale, waive, control)
    finally:
        try:
            conn.send(("quit",))
        except (OSError, ValueError):
            pass
        child.join(30)
        if child.is_alive():
            child.kill()
            child.join()


def _serve_and_measure(conn, bench, cell, config, seed, seconds, trace,
                       t_start, scale, waive, control):
    workload = cell["name"]

    import jax

    from kolibrie_tpu.frontends import http_server
    from kolibrie_tpu.obs import spans as prog_spans
    from kolibrie_tpu.ops import pallas_kernels
    from kolibrie_tpu.query import compile_cache

    # before the first lowering; where JAX_COMPILATION_CACHE_DIR is set the
    # program records it and sets no other
    cache_dir = compile_cache.enable(explicit_dir=files.path(".jax_cache"))
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu" and device["count"] >= cell["chips"]
    if not on_chip and scale is None and "platform_is_tpu" not in waive:
        print(f"benchmark: cell {workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {device}", file=sys.stderr)
        return None, NO_CHIP_EXIT
    if on_chip:
        peaks = files.read_json("data", "peaks.json")["peaks"]
        if device["kind"] not in peaks:
            raise SystemExit(f"benchmark: no peaks for device {device['kind']!r}")
    say(phase="start", workload=workload, seed=seed, seconds=seconds, trace=trace,
        device=device, jax=jax.__version__, compile_cache_dir=cache_dir,
        scale_override=scale)

    checks = []

    def check(name, value, limit, ok):
        checks.append({"check": name, "value": value, "limit": limit,
                       "ok": bool(ok), "waived": name in waive})
        say(**checks[-1])

    def answer():
        if not conn.poll(ANSWER_TIMEOUT_S):
            raise RuntimeError("the load generator did not answer")
        return conn.recv()

    def ask(*cmd):
        conn.send(cmd)
        return answer()

    phases, quantities = {}, {}
    httpd = http_server.make_server("127.0.0.1", 0, quiet=True, data_dir=None)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        port = httpd.server_address[1]
        generated = answer()  # to the "generate" sent before JAX started
        phases["generate"] = generated["seconds"]
        quantities["triples"] = n_triples = generated["triples"]
        loaded = ask("load", port)
        phases["load"] = loaded["seconds"]
        db = httpd.RequestHandlerClass.state.stores[loadgen.STORE_ID].db
        say(phase="load", triples=loaded["acknowledged"], generated=n_triples,
            generate_s=phases["generate"], load_s=phases["load"])
        check("load_acknowledged_all_triples", loaded["acknowledged"], n_triples,
              loaded["acknowledged"] == n_triples)
        check("store_mode", db.execution_mode, config["store_mode"],
              db.execution_mode == config["store_mode"])
        check("scale_as_configured", scale, None, scale is None)

        # warm every template of the cycle, as often and with as many
        # clients as the traffic file says, so the cap advisor's re-runs and
        # every compile are over
        clients = generated["clients"]
        t0, got = time.perf_counter(), {"ms": 0.0}
        for k, n in enumerate(generated["warmup_counts"]):
            got = ask("cycle", k, "warmup", n)
            say(phase="warmup", cycle=k, clients=n, ms=got["ms"],
                statuses=sorted({r["status"] for r in got["requests"]}))
        phases["warmup"] = time.perf_counter() - t0

        cl = Client(port, 60_000)  # this process reads /metrics and /stats only
        trace_dir = files.path(".traces", f"{workload}-{seed}")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            prog_spans.set_ring_capacity(RING_CAPACITY)
            prog_spans.clear()
        counters0 = _counters(cl)

        # ---- the window: whole cycles, closed loop.  One client: the first
        # cycle is always sent, a further one only if the longest cycle seen
        # so far would still end inside ``seconds``.  Several clients run
        # free, each by that rule (``loadgen.free_run``)
        requests, cycles, anchors = [], [], []
        tracing, traced_s, t_trace = "before", 0.0, 0.0
        longest_ms = got["ms"]
        # trace from the window's second cycle, or from its first where the
        # window holds no second one
        first_traced = 1 if 2 * longest_ms < seconds * 1000.0 else 0
        gc_runs = [s["collections"] for s in gc.get_stats()]

        def start_trace():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # the anchor between the wall clock and the trace's clock
            anchors.append(time.time())
            return "on", time.perf_counter()

        def note(got):
            requests.extend(got["requests"])
            if got["t1"] is not None:  # a whole cycle
                cycles.append({"k": len(cycles), "ms": got["ms"], "t0": got["t0"],
                               "t1": got["t1"],
                               "trace_ids": [r["trace_id"] for r in got["requests"]]})

        def stop_trace():
            jax.profiler.stop_trace()
            return "done", time.perf_counter() - t_trace

        setup_s = time.perf_counter() - t_start
        t_open = time.perf_counter()
        if clients > 1:
            # the clients run free for the whole window; the trace holds its
            # first ``trace_min_seconds``, or all of it
            if trace:
                tracing, t_trace = start_trace()
            conn.send(("free", "window", seconds))
            if trace:
                with jax.profiler.TraceAnnotation(xplane.ANNOTATION):
                    conn.poll(generated["trace_min_seconds"])
                tracing, traced_s = stop_trace()
            for got in answer():
                note(got)
        k = 0
        while clients == 1 and (k == 0 or time.perf_counter() - t_open
                                + longest_ms / 1000.0 < seconds):
            if trace and tracing == "before" and k >= first_traced:
                tracing, t_trace = start_trace()
            elif tracing == "on":
                anchors.append(time.time())
            if tracing == "on":
                # the traced window: one annotation a traced cycle
                with jax.profiler.TraceAnnotation(xplane.ANNOTATION):
                    got = ask("cycle", k, "window", clients)
            else:
                got = ask("cycle", k, "window", clients)
            note(got)
            longest_ms = max(longest_ms, got["ms"]) if k else got["ms"]
            k += 1
            if tracing == "on" and time.perf_counter() - t_trace >= generated[
                    "trace_min_seconds"]:
                tracing, traced_s = stop_trace()
        if tracing == "on":
            tracing, traced_s = stop_trace()
        window_s = time.perf_counter() - t_open
        counters1 = _counters(cl)
        # every request carries a trace id, so the program's ring holds the
        # spans of the window's last requests in any run (all of them in a
        # traced run, whose ring is large)
        span_list = prog_spans.spans_snapshot()
        gc_runs = [s["collections"] - n for s, n in zip(gc.get_stats(), gc_runs)]
        stats = cl.get_json("/stats")["stores"][loadgen.STORE_ID]
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        device["memory_peak_bytes"] = max(
            (m.get("peak_bytes_in_use", 0) for m in mem), default=0)
        statuses = Counter(ask("statuses")) + cl.statuses
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.join(timeout=30)

    # ---- outside the window and outside set-up: the plain reference, in the
    # load generator's process (it kept the bodies)
    compared = ask("compare", control)
    bad, reference_s = compared["wrong"], compared["reference_s"]
    say(phase="compare", requests=len(requests), reference_s=reference_s,
        distinct_texts=compared["distinct_texts"],
        rows_by_template=compared["rows_by_template"])
    check("wrong_or_failed_answers", len(bad), 0, not bad)
    check("templates_with_empty_reference_answer", compared["empty"], [],
          not compared["empty"])
    check("whole_cycles_in_window", len(cycles), ">=1", len(cycles) >= 1)
    if control:
        say(phase="control", **compared["control"])

    def delta(name):
        key = "metrics." + name
        return counters1.get(key, 0.0) - counters0.get(key, 0.0)

    on_device = delta('kolibrie_query_seconds_count{path="device"}')
    batched = delta("kolibrie_query_batched_total")
    degraded = counters1.get('metrics.kolibrie_query_seconds_count{path="degraded"}', 0.0)
    host_path = delta('kolibrie_query_seconds_count{path="host"}')
    check("platform_is_tpu", device["platform"], "tpu", device["platform"] == "tpu")
    check("chips", device["count"], cell["chips"], device["count"] >= cell["chips"])
    check("pallas_enabled_not_interpreted",
          [pallas_kernels.pallas_enabled(), pallas_kernels._interpret()],
          [True, False],
          pallas_kernels.pallas_enabled() and not pallas_kernels._interpret())
    check("window_requests_on_device_path", on_device + batched, len(requests),
          on_device + batched == len(requests))
    check("none_degraded_or_on_host", [degraded, host_path], [0, 0],
          degraded == 0 and host_path == 0)
    check("no_sticky_lowering_failure", stats["plan_cache"]["sticky_failures"], 0,
          stats["plan_cache"]["sticky_failures"] == 0)
    bad_breakers = {fp: b for fp, b in stats["breakers"].items()
                    if b["state"] != "closed" or b["total_failures"]}
    check("no_breaker_open_or_failed", bad_breakers, {}, not bad_breakers)
    check("only_http_200", {str(s): n for s, n in sorted(statuses.items())},
          "only 200", set(statuses) == {200})
    if cell["chips"] > 1:
        _mesh_proof(check, db, stats, config, requests, counters0, counters1)

    run = {"cycles": cycles, "requests": requests, "setup_s": setup_s}
    by_template = {}
    for r in requests:
        by_template.setdefault(r["template"], []).append(r["ms"])
    say(phase="window", window_s=window_s, whole_cycles=len(cycles),
        clients=clients, requests=len(requests),
        seconds=seconds, cycle_ms_median=(
            sorted(c["ms"] for c in cycles)[len(cycles) // 2] if cycles else None),
        cycle_ms_all=[c["ms"] for c in cycles][:64],
        latency_ms_count_min_p25_p50_p75_p95_max={
            name: [len(v)] + np.percentile(v, [0, 25, 50, 75, 95, 100]).tolist()
            for name, v in by_template.items()},
        phases=phases, reference_s=reference_s,
        compile_counters={k: v for k, v in counters1.items()
                          if k.startswith("compile")})

    if clients > 1:
        # how the batcher grouped each cycle's clients, where the ring still
        # holds the spans: the sizes of its dispatches, and the time from the
        # first to the last of the cycle's first requests reaching it
        groups, arrived = {}, {}
        for sp in span_list:
            if not sp["trace_id"].startswith("bench-window-"):
                continue
            k, step = sp["trace_id"].split("-")[2:4]
            if sp["name"] == "batcher.dispatch":
                groups.setdefault(int(k), []).append(sp.get("attrs", {}).get("batch"))
            elif sp["name"] == "batcher.submit" and step == "0":
                arrived.setdefault(int(k), []).append(sp["start_s"])
        say(phase="groups", dispatch_sizes_by_cycle=groups,
            first_requests_arrive_within_ms={
                k: (max(v) - min(v)) * 1000.0 for k, v in arrived.items()})

    # where a far-off request spent its time: the spans of the window's
    # slowest request, where the ring still holds them
    slowest = max(requests, key=lambda r: r["ms"])
    say(phase="slowest_request", template=slowest["template"], cycle=slowest["cycle"],
        ms=slowest["ms"], cap_retries_in_window=delta(
            'kolibrie_cap_retries_total{engine="device"}'),
        server_gc_collections_in_window=gc_runs,
        spans=[[sp["name"], sp["dur_ms"]] for sp in span_list
               if sp["trace_id"] == slowest["trace_id"]])

    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics, breakdown = {}, None
    if not trace:
        for m in layers.declared(bench, "end_to_end", workload):
            value = e2e.METRICS[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        reduced = None
        if tracing == "done":
            reduced = xplane.reduce(xplane.load(xplane.find_trace(trace_dir)))
            shutil.rmtree(trace_dir, ignore_errors=True)
        by_trace = {}
        for sp in span_list:
            by_trace.setdefault(sp["trace_id"], []).append(sp)
        ctx = {"cycles": cycles, "spans_by_trace": by_trace,
               "counters0": counters0, "counters1": counters1,
               "phases": phases, "quantities": quantities, "trace": reduced}
        metrics = layers.read_all(bench, workload, ctx)
        if reduced and reduced["devices"]:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            starts = [a for a, _ in reduced["annotations"]]
            offset = 0.0
            if anchors and len(anchors) == len(starts):
                diffs = sorted(w - a / 1e9 for w, a in zip(anchors, starts))
                offset = diffs[len(diffs) // 2]
            breakdown = {
                "device_ops": [[n, s] for n, s in sorted(
                    reduced["top"].items(), key=lambda kv: -kv[1])[:10]],
                "idle_gaps": xplane.name_gaps(
                    reduced["gaps"], span_list, offset, reduced["annotations"]),
            }
            say(phase="trace", traced_s=traced_s, busy_s=reduced["busy_s"],
                window_s=reduced["window_s"], devices=reduced["devices"],
                device_lines=reduced["lines"],
                custom_calls={n: s for n, s in reduced["any"].items() if ":" in n},
                collectives={n: s for n, s in reduced["any"].items()
                             if COLLECTIVE.search(n)},
                self_time_top=sorted(reduced["self"].items(),
                                     key=lambda kv: -kv[1])[:25])
        check("device_ran_ops_in_trace", device.get("busy_s", 0.0), ">0",
              device.get("busy_s", 0.0) > 0)

    failed = [c["check"] for c in checks if not c["ok"] and not c["waived"]]
    if failed:
        say(phase="failed_checks", checks=failed)
    result = {"correct": not failed, "attempted": len(requests),
              "failed": len(bad), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # each number compared beside its limit: last in the result line, and
    # the last lines on standard error
    result["checks"] = {c["check"]: {"value": c["value"], "limit": c["limit"],
                                     "ok": c["ok"]} for c in checks}
    for c in checks:
        print(f"check {c['check']}: {json.dumps(c['value'], default=str)} "
              f"(limit {json.dumps(c['limit'], default=str)}) "
              f"{'ok' if c['ok'] else 'NOT OK'}{' (waived)' if c['waived'] else ''}",
              file=sys.stderr, flush=True)
    return result, 0 if not failed else 1
