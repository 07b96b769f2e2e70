"""By-hand checks of the host-sync account (ISSUE 38), one cell at a time.

    python tools/sync_audit.py guard --workload <cell> [--rows N] [--seed S]
    python tools/sync_audit.py idle --workload <cell> --seed S --seconds T

`guard`: is every device->host read of the cell's queries made inside
`phases.device_wait`? The cell's tables at about N lineitem rows (the
harness's --rehearse-rows scale), its query list once to warm, then once
more under a process-wide `jax.transfer_guard_device_to_host(
"disallow_explicit")` with the door alone allowing transfers: PATCHED
HERE, never in the package. Array reads through `ArrayImpl._value` (int(),
np.asarray on the TPU, device_get, tolist) outside the door are recorded
with their call site and let through, so one pass lists them all; any other
transfer the guard stops fails its query and is printed. On the CPU the
guard never fires and a plain array hands numpy its buffer, so there the
check also wraps np.asarray / np.array: a partial check only.

`idle`: one `benchmark/run.py --trace 1` run of the cell, and from the
same capture the device's idle time inside `rapids.sync.*` (by site) and
`rapids.pipeline.stall` spans against outside them, with the traced
queries' syncs by site (count and ms a query). The harness deletes its
trace once reduced; this wraps the reduction to look first.

Each prints one JSON line last.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import tempfile
import threading
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

PKG = os.sep + "spark_rapids_tpu" + os.sep


def _site_of(stack) -> str:
    for fr in reversed(stack):
        if PKG in fr.filename:
            return f"{fr.filename.split(PKG)[1]}:{fr.lineno} {fr.name}"
    return "outside the package"


class _Guard:
    """The door allows, everything else is watched (module docstring)."""

    def __init__(self):
        self.tl = threading.local()
        self.strays = collections.Counter()
        self.stacks = {}
        self.prefetches = 0
        self.on = False

    def note(self, kind: str) -> None:
        if not self.on or getattr(self.tl, "depth", 0):
            return
        st = traceback.extract_stack()[:-3]
        if any("ir_constant" in fr.name for fr in st):
            return   # a constant read while a program compiles: no query's
        key = f"{kind} {_site_of(st)} [{threading.current_thread().name}]"
        self.strays[key] += 1
        self.stacks.setdefault(key, [f"{fr.filename.split(os.sep)[-1]}:"
                                     f"{fr.lineno} {fr.name}"
                                     for fr in st[-10:]])

    def install(self):
        import jax
        import numpy as np
        from jax._src import array as A
        from spark_rapids_tpu.runtime.obs import phases as PH
        cls, g = A.ArrayImpl, self
        value = cls.__dict__["_value"]
        allow = jax.transfer_guard_device_to_host

        def _value(arr):
            if arr._npy_value is None:
                g.note("read")
            with allow("allow"):
                return value.fget(arr)
        cls._value = property(_value)
        prefetch = cls.copy_to_host_async

        def copy_to_host_async(arr):
            g.prefetches += 1   # a transfer started, not a wait
            with allow("allow"):
                return prefetch(arr)
        cls.copy_to_host_async = copy_to_host_async
        for name in ("asarray", "array", "ascontiguousarray"):
            f = getattr(np, name)

            def wrapped(*a, _f=f, _n=name, **k):
                if a and isinstance(a[0], jax.Array) \
                        and not isinstance(a[0], jax.core.Tracer):
                    g.note("np." + _n)
                return _f(*a, **k)
            setattr(np, name, wrapped)
        enter, leave = PH._DeviceWait.__enter__, PH._DeviceWait.__exit__

        def door_enter(w):
            ctx = allow("allow")
            ctx.__enter__()
            g.tl.ctxs = getattr(g.tl, "ctxs", []) + [ctx]
            g.tl.depth = len(g.tl.ctxs)
            return enter(w)

        def door_exit(w, *exc):
            try:
                return leave(w, *exc)
            finally:
                g.tl.ctxs.pop().__exit__(*exc)
                g.tl.depth = len(g.tl.ctxs)
        PH._DeviceWait.__enter__ = door_enter
        PH._DeviceWait.__exit__ = door_exit


def _cell(workload: str, rows: int, seed: int):
    """(session, texts, order, device platform) of the cell at `rows`."""
    import importlib
    import run as H
    cell, _ = H.resolve(workload)
    config = H.load_json(BENCH, "configs", f"{cell['config']}.json")
    traffic = H.load_traffic(cell["traffic"])
    texts = {q: H.load_query(q) for q in traffic["queries"]}
    datagen = importlib.import_module(config["generator"])
    tables = datagen.generate(rows / H.LINEITEM_ROWS_PER_SF, seed)
    from spark_rapids_tpu.sql.session import TpuSession
    sess = TpuSession()
    H.place(sess, config, tables, tempfile.mkdtemp(prefix="sync-audit-"),
            datagen)
    return sess, texts, traffic["queries"]


def guard(args) -> dict:
    import jax
    from spark_rapids_tpu.runtime import obs
    g = _Guard()
    g.install()
    sess, texts, order = _cell(args.workload, args.rows, args.seed)
    for q in order:                      # warm: compiles, caches
        sess.sql(texts[q]).to_pydict()
    failed = {}
    jax.config.update("jax_transfer_guard_device_to_host",
                      "disallow_explicit")
    g.on = True
    try:
        for q in order:
            try:
                sess.sql(texts[q]).to_pydict()
            except Exception as e:  # noqa: BLE001 - a guarded transfer
                failed[q] = "".join(traceback.format_exception(e))[-3000:]
    finally:
        g.on = False
        jax.config.update("jax_transfer_guard_device_to_host", "allow")
    recs = obs.recent_queries(len(order))
    for key, stack in g.stacks.items():
        print(key, "\n   ", "\n    ".join(stack), file=sys.stderr)
    for q, tb in failed.items():
        print(f"query {q} stopped by the guard:\n{tb}", file=sys.stderr)
    return {"workload": args.workload, "rows": args.rows,
            "platform": jax.devices()[0].platform,
            "strays": dict(g.strays), "stopped": sorted(failed),
            "prefetches": g.prefetches,
            "syncs": {q: r.get("syncs") for q, r in zip(order, recs)}}


def _inside(intervals, spans) -> int:
    """ns of `intervals` that lie inside the union of `spans`."""
    import trace_reduce as TRd
    spans = TRd._union(spans)
    out, j = 0, 0
    for s, e in sorted(intervals):
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            out += min(e, spans[k][1]) - max(s, spans[k][0])
            k += 1
    return out


def _idle_split(planes) -> dict:
    """Device idle ns of the annotated window, inside each kind of
    rapids.sync.* / rapids.pipeline.stall span and outside them all,
    summed over devices."""
    import trace_reduce as TRd
    host, passes = collections.defaultdict(list), []
    for plane, lines in planes.items():
        if plane.startswith("/device:"):
            continue
        for events in lines.values():
            for name, start, dur in events:
                if name == TRd.PASS_ANNOTATION:
                    passes.append((start, start + dur))
                elif name.startswith(("rapids.sync.", "rapids.pipeline.stall")):
                    host[name].append((start, start + dur))
    lo, hi = min(s for s, _ in passes), max(e for _, e in passes)
    gaps = []
    for plane, lines in planes.items():
        if not plane.startswith(TRd.DEVICE_PREFIX) or TRd.OPS_LINE not in lines:
            continue
        ops = TRd._union(TRd._clip([(s, s + d) for _, s, d
                                    in lines[TRd.OPS_LINE]], lo, hi))
        if not ops:
            continue
        edges = [lo] + [t for iv in ops for t in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    out = {name: _inside(gaps, spans) / 1e9 for name, spans in host.items()}
    idle = sum(e - s for s, e in gaps) / 1e9
    inside = _inside(gaps, [iv for spans in host.values() for iv in spans])
    return {"idle_s": idle, "inside_s": inside / 1e9,
            "outside_s": idle - inside / 1e9, "by_span_s": out}


def idle(args) -> dict:
    import run as H
    import trace_reduce as TRd
    seen = {}
    reduce = TRd.reduce_trace

    def looking(planes, *a, **k):
        out = reduce(planes, *a, **k)
        if out:
            seen.update(_idle_split(planes), passes=out["passes"])
        return out
    TRd.reduce_trace = looking
    rc = H.main(["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "1"]
                + (["--rehearse-rows", str(args.rows)] if args.rehearse
                   else []))
    from spark_rapids_tpu.runtime import obs
    cell, _ = H.resolve(args.workload)
    per_pass = len(H.load_traffic(cell["traffic"])["queries"])
    passes = seen.get("passes") or 0
    recs = [r for r in obs.recent_queries() if "syncs" in r]
    n = passes * per_pass if passes else len(recs)
    recs = recs[-n:] if n else recs
    sites = collections.defaultdict(lambda: [0.0, 0.0])
    for r in recs:
        for site, (c, ns) in r["syncs"].items():
            sites[site][0] += c / len(recs)
            sites[site][1] += ns / 1e6 / len(recs)
    return {"workload": args.workload, "rc": rc, "idle_split": seen,
            "queries": len(recs),
            "syncs_a_query": {s: [round(c, 3), round(ms, 3)]
                              for s, (c, ms) in sorted(sites.items())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("guard", "idle"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3800000001)
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--seconds", type=float, default=48)
    ap.add_argument("--rehearse", action="store_true",
                    help="idle: the harness's CPU rehearsal at --rows")
    args = ap.parse_args(argv)
    out = guard(args) if args.mode == "guard" else idle(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
