"""Documentation generators.

Reference parity: TypeChecks.scala SupportedOpsDocs.main (:2153) /
SupportedOpsForTools.main (:2360), which render the rule registries to
docs/supported_ops.md and tools/generated_files CSVs, and RapidsConf's
`help` main (:2505) rendering docs/configs.md. Run:

    python tools/gen_docs.py          # writes docs/ + tools/generated_files/
"""
from __future__ import annotations

import csv
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def gen_configs_md() -> str:
    from spark_rapids_tpu.config import generate_docs
    return generate_docs()


def gen_supported_ops() -> str:
    from spark_rapids_tpu.plan.overrides import AGG_RULES, EXPR_RULES
    lines = [
        "# Supported operators and expressions",
        "",
        "Generated by `tools/gen_docs.py` from the live rule registries",
        "(the analog of the reference's supported_ops.md generated from",
        "TypeChecks.scala). An operator outside these tables falls back to",
        "the CPU interpreter per-node, never failing the query.",
        "",
        "## Exec operators",
        "",
        "| Exec | Notes |",
        "|---|---|",
        "| InMemorySource / ParquetScan / TextScan (csv, json, orc) / Range / CachedRelation | sources; strings upload dictionary-encoded |",
        "| Project / Filter | fused whole-stage XLA kernels; filters produce selection masks (no compaction) |",
        "| FusedStage (project/filter/expand/limit chains) | whole-stage vertical fusion (spark.rapids.sql.stageFusion.enabled): maximal linear chains compose into ONE dispatch per batch per stage, rendered `*(N)` in explain(mode='stages') |",
        "| Aggregate | global, sort-based grouped, and MXU-bucketed fast path for low-cardinality keys; filter conditions and whole narrow-operator chains fuse into the update kernel |",
        "| WindowNode | rank family, ntile, lead/lag, framed sum/count/min/max/avg as segmented scans |",
        "| Sort | in-core fused sort; out-of-core host-staged path over the byte threshold |",
        "| Join (broadcast, shuffled hash) | inner/left/right/full/semi/anti + cross; skew sub-partitioning |",
        "| Limit / Union / Expand | |",
        "| Exchanges (hash, round-robin, range, collect) | single-dispatch counting-sort compaction (spark.rapids.shuffle.partitioning=compact); masked zero-copy slicing as escape hatch |",
        "",
        "## Expressions",
        "",
        "| Expression | Input types | Notes |",
        "|---|---|---|",
    ]
    for cls, rule in sorted(EXPR_RULES.items(), key=lambda kv: kv[1].name):
        sig = ", ".join(sorted(rule.input_sig.tags)).lower()
        lines.append(f"| {rule.name} | {sig} | {rule.doc} |")
    lines += ["", "## Aggregate functions", "",
              "| Aggregate | Input types | Notes |", "|---|---|---|"]
    for cls, rule in sorted(AGG_RULES.items(), key=lambda kv: kv[1].name):
        sig = ", ".join(sorted(rule.input_sig.tags)).lower()
        lines.append(f"| {rule.name} | {sig} | {rule.doc} |")
    lines.append("")
    return "\n".join(lines)


def gen_metrics_md() -> str:
    """Render the metric-name registry (runtime/metrics.py module
    constants + the runtime/trace.py task-metric roster) with their
    in-source `#:` doc comments. tpulint's TPU-L007 cross-checks every
    `.metric("...")` site against this file, so an undocumented or
    ad-hoc metric name fails the lint instead of silently vanishing
    from rollups."""
    import ast

    def constants_with_docs(path):
        src = open(path).read()
        lines = src.splitlines()
        tree = ast.parse(src, path)
        out = []
        for stmt in tree.body:
            if not (isinstance(stmt, ast.Assign)
                    and isinstance(stmt.value, ast.Constant)
                    and isinstance(stmt.value.value, str)):
                continue
            tgt = stmt.targets[0]
            if not (isinstance(tgt, ast.Name) and tgt.id.isupper()):
                continue
            doc = []
            ln = stmt.lineno - 1
            while ln >= 1 and lines[ln - 1].lstrip().startswith("#:"):
                doc.insert(0, lines[ln - 1].lstrip()[2:].strip())
                ln -= 1
            out.append((stmt.value.value, tgt.id, " ".join(doc)))
        return out

    mpath = os.path.join(ROOT, "spark_rapids_tpu", "runtime", "metrics.py")
    consts = constants_with_docs(mpath)
    from spark_rapids_tpu.runtime.trace import TASK_METRIC_NAMES
    documented = {name for name, _, _ in consts}
    lines = [
        "# Metric names",
        "",
        "Generated by `tools/gen_docs.py` from the `runtime/metrics.py`",
        "constants and the `runtime/trace.py` task-metric roster (the",
        "GpuMetric / GpuTaskMetrics registries). Every string-literal",
        "`.metric(\"...\")` site in the engine must use one of these names",
        "— `tools/tpulint.py` rule TPU-L007 enforces it.",
        "",
        "## Exec metrics (runtime/metrics.py)",
        "",
        "| Name | Constant | Notes |",
        "|---|---|---|",
    ]
    for value, const, doc in consts:
        lines.append(f"| `{value}` | {const} | {doc} |")
    lines += [
        "",
        "## Per-task accumulators (runtime/trace.py TASK_METRIC_NAMES)",
        "",
        "Rolled into `query_<n>_events.jsonl` at task completion",
        "(the GpuTaskMetrics analog).",
        "",
    ]
    for name in TASK_METRIC_NAMES:
        star = "" if name in documented else " (task-roster only)"
        lines.append(f"- `{name}`{star}")
    from spark_rapids_tpu.runtime.obs.attribution import BUCKETS
    lines += [
        "",
        "## Attribution buckets (runtime/obs/attribution.py BUCKETS)",
        "",
        "Per-query wall-time attribution phases: rendered by",
        "`explain(mode=\"analyze\")` and the history server, carried in",
        "history records, exported as",
        "`rapids_query_seconds_bucket{phase=...}` on /metrics, and",
        "summarized on /healthz for SLO breaches. Every",
        "`attribution.record(\"...\")` literal in the engine must use one",
        "of these names — `tools/tpulint.py` rule TPU-L009 enforces it.",
        "",
        "| Bucket | Notes |",
        "|---|---|",
    ]
    for name, doc in BUCKETS.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.runtime.obs import phases as PH
    lines += [
        "",
        "## Query phases (runtime/obs/phases.py SPANS)",
        "",
        "The seams of a top-level action, each one span (level ESSENTIAL,",
        "category `query`; `rapids.<span>` in a profiler capture) and one",
        "clock of the per-query phase account, whose records",
        "`obs.recent_queries()` returns (newest " + str(PH.RING_SIZE) + ";",
        "`phases_ns`, `timers_ns`, `counters`: the module's docstring says",
        "what each holds).",
        "",
        "| Phase | Span |",
        "|---|---|",
    ]
    for phase, span_name in PH.SPANS.items():
        lines.append(f"| `{phase}` | `{span_name}` |")
    from spark_rapids_tpu.runtime.faults import BYTE_SITES, KINDS, SITES
    lines += [
        "",
        "## Fault sites (runtime/faults.py SITES)",
        "",
        "Named engine crossings the fault injector can target via",
        "`spark.rapids.debug.faults` (kinds: " + ", ".join(KINDS) + ";",
        "`corrupt` is data-site-only). Every `faults.site(\"...\")` /",
        "`site_bytes(\"...\")` literal must use one of these names —",
        "`tools/tpulint.py` rule TPU-L008 enforces it. Fired faults",
        "count into `rapids_faults_injected_total{site=...}` and the",
        "per-site /healthz tally.",
        "",
        "| Site | Notes |",
        "|---|---|",
    ]
    for name, doc in SITES.items():
        star = " (data site)" if name in BYTE_SITES else ""
        lines.append(f"| `{name}`{star} | {doc} |")
    from spark_rapids_tpu.runtime.obs import (EXEC_FALLBACK_SITES,
                                              PALLAS_SEGSUM_PATHS)
    lines += [
        "",
        "## Stage fallbacks (runtime/obs EXEC_FALLBACK_SITES)",
        "",
        "A stage whose trace, compile or setup fails keeps the query",
        "correct on a slower replay path. Each such fallback counts into",
        "`rapids_stage_fallbacks_total{site=...}` (sites: "
        + ", ".join(f"`{s}`" for s in EXEC_FALLBACK_SITES) + "), so a",
        "run on real hardware can fail on a dead fast path without",
        "reading logs (`chip_smoke.py` does).",
        "`rapids_pallas_segsum_traces_total{path=...}` (paths: "
        + ", ".join(f"`{p}`" for p in PALLAS_SEGSUM_PATHS) + ") counts,",
        "at trace time, the group-by traces whose eligibility gates",
        "routed them into the Pallas sorted-window segmented sum.",
    ]
    from spark_rapids_tpu.runtime.obs.live import STATES
    lines += [
        "",
        "## Query states (runtime/obs/live.py STATES)",
        "",
        "The live query registry's state machine: queued -> planning ->",
        "executing -> finishing -> {ok, failed, degraded}. Surfaced by",
        "`session.running_queries()`, the `/queries` endpoint and the",
        "`/console` page. Every `transition(\"...\")` literal in the",
        "engine must use one of these names — `tools/tpulint.py` rule",
        "TPU-L011 enforces it.",
        "",
        "| State | Notes |",
        "|---|---|",
    ]
    for name, doc in STATES.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.runtime.obs.sampler import SERIES
    lines += [
        "",
        "## Sampler series (runtime/obs/sampler.py SERIES)",
        "",
        "Resource time-series sampled into bounded rings by the",
        "always-on sampler (`spark.rapids.obs.sampler.*`): exported as",
        "`rapids_sampler_<series>` gauges on /metrics, rendered as",
        "sparklines on `/console`, and embedded as Chrome counter",
        "tracks in every flight-recorder dump. Every sampler-series",
        "literal must use one of these names — `tools/tpulint.py` rule",
        "TPU-L011 enforces it.",
        "",
        "| Series | Notes |",
        "|---|---|",
    ]
    for name, doc in SERIES.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.runtime.obs.endpoint import ROUTES
    lines += [
        "",
        "## HTTP routes (runtime/obs/endpoint.py ROUTES)",
        "",
        "Every route the obs endpoint serves",
        "(`spark.rapids.obs.port`), including the serving layer's",
        "mutating routes (`spark.rapids.serving.enabled`). Every",
        "`path == \"...\"` dispatch literal in the endpoint must use one",
        "of these routes — `tools/tpulint.py` rule TPU-L014 enforces it",
        "(and flags stale or undocumented roster entries).",
        "",
        "| Route | Notes |",
        "|---|---|",
    ]
    for name, doc in ROUTES.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.runtime.obs.reqtrace import (REQUEST_SPANS,
                                                       VERDICTS)
    lines += [
        "",
        "## Serving request spans (runtime/obs/reqtrace.py"
        " REQUEST_SPANS)",
        "",
        "The serving layer's own span tree over a POST /sql request",
        "(`spark.rapids.obs.reqtrace.enabled`): these phases frame the",
        "engine's exec spans in every exported per-request timeline,",
        "joined by the query id. Every `request_span(\"...\")` literal",
        "must use one of these names — `tools/tpulint.py` rule TPU-L015",
        "enforces it (and flags stale or undocumented roster entries).",
        "",
        "| Span | Notes |",
        "|---|---|",
    ]
    for name, doc in REQUEST_SPANS.items():
        lines.append(f"| `{name}` | {doc} |")
    lines += [
        "",
        "## Tail-sampling verdicts (runtime/obs/reqtrace.py VERDICTS)",
        "",
        "The per-request export decision at request end, counted into",
        "`rapids_reqtrace_verdicts_total{verdict=...}` and embedded in",
        "exported timeline filenames",
        "(`req_<seq>_<verdict>_<trace8>.json`). Always-keep verdicts",
        "bypass the export rate limit; only `sampled` is throttled and",
        "only `dropped` discards the buffer. Every `_v(\"...\")` verdict",
        "checkpoint must use one of these names — `tools/tpulint.py`",
        "rule TPU-L015 enforces it.",
        "",
        "| Verdict | Notes |",
        "|---|---|",
    ]
    for name, doc in VERDICTS.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.analysis.kernel_audit import KERNEL_PRIMITIVES
    lines += [
        "",
        "## Kernel-primitive modules "
        "(analysis/kernel_audit.py KERNEL_PRIMITIVES)",
        "",
        "Kernel-emitting modules the cost auditor's coverage statement",
        "rests on: every `compile_cache.jit` decoration/call and every",
        "`pallas_call` site must live in one of these modules —",
        "`tools/tpulint.py` rule TPU-L013 enforces it (and flags stale",
        "entries). The golden cost-signature artifact",
        "(`tests/golden_plans/cost_signatures.json`, generated by",
        "`tools/gen_dispatch_budgets.py`) embeds this roster.",
        "",
        "| Module | Notes |",
        "|---|---|",
    ]
    for name, doc in KERNEL_PRIMITIVES.items():
        lines.append(f"| `{name}` | {doc} |")
    from spark_rapids_tpu.parallel.mesh import SANCTIONED_COLLECTIVE_MODULES
    lines += [
        "",
        "## Collective modules "
        "(parallel/mesh.py SANCTIONED_COLLECTIVE_MODULES)",
        "",
        "Modules allowed to invoke XLA collective primitives",
        "(`lax.all_to_all`, `lax.psum`, `shard_map`). Collectives are",
        "SPMD program structure — every shard must reach them or the",
        "mesh deadlocks — so call sites stay confined to the rostered",
        "exchange/planner modules, where the multichip compile keys",
        "(`mesh_fingerprint`) and the shard-skew audit can see them.",
        "`tools/tpulint.py` rule TPU-L016 enforces it (and flags stale",
        "or undocumented roster entries).",
        "",
        "| Module | Notes |",
        "|---|---|",
    ]
    for name, doc in SANCTIONED_COLLECTIVE_MODULES.items():
        lines.append(f"| `{name}` | {doc} |")
    lines += [
        "",
        "## Roofline attribution gauges (analysis/kernel_audit.py)",
        "",
        "Set once per audited query end"
        " (`spark.rapids.obs.audit.enabled`), labelled by kernel group",
        "(`device_compute`, `shuffle`, plus `total` for the first two):",
        "",
        "- `rapids_roofline_achieved_gbps` — audited bytes over"
        " measured device seconds",
        "- `rapids_roofline_pct` — share of the configured"
        " `spark.rapids.obs.audit.peakGbps` roofline",
        "- `rapids_roofline_achieved_gflops` — audited FLOPs over"
        " measured device seconds",
        "- `rapids_roofline_padding_waste_ratio` — worst-case"
        " shape-bucket padding share of input plane bytes",
        "",
        "The full per-query roofline doc (groups, boundedness verdict,",
        "per-kernel-family detail) rides in history records and",
        "`explain(mode=\"analyze\")`; `tools/roofline_report.py`",
        "aggregates it across the history store.",
    ]
    from spark_rapids_tpu.exec.adaptive import (BROADCAST_CONVERSION,
                                                BUILD_REUSE, MEASURED_COST,
                                                SKEW_SPLIT)
    lines += [
        "",
        "## Adaptive execution counters (exec/adaptive.py)",
        "",
        "Every adaptive replan (`spark.rapids.sql.adaptive.enabled`)",
        "increments `rapids_aqe_decisions_total{kind=...}`; decisions",
        "that drop device dispatches also add the drop to",
        "`rapids_aqe_dispatches_saved_total{kind=...}`. Kinds:",
        "",
    ]
    for kind, doc in ((BROADCAST_CONVERSION, "shuffle-hash join whose"
                       " measured build side fit under"
                       " `spark.rapids.sql.adaptive.broadcastThresholdBytes`"
                       " replanned as a broadcast join"),
                      (SKEW_SPLIT, "a shuffle output partition over"
                       " `skewFactor` x the median row count split into"
                       " sub-dispatches"),
                      (BUILD_REUSE, "a broadcast build adopted from a prior"
                       " query (same plan digest + table versions) instead"
                       " of rebuilt"),
                      (MEASURED_COST, "history cost signatures replanned"
                       " partition counts / fusion boundaries / tiny-batch"
                       " coalescing before dispatch")):
        lines.append(f"- `kind=\"{kind}\"` — {doc}")
    lines += [
        "",
        "Each decision also emits an `aqeDecision` trace instant and an",
        "entry in the query's `aqe` history-record field (rendered by",
        "`explain(mode=\"analyze\")`'s adaptive section and",
        "`tools/roofline_report.py`'s adaptive column).",
    ]
    lines.append("")
    return "\n".join(lines)


def gen_csvs(outdir: str) -> None:
    from spark_rapids_tpu.plan.overrides import AGG_RULES, EXPR_RULES
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "supportedExprs.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["Expression", "InputTypes", "Notes"])
        for _, rule in sorted(EXPR_RULES.items(), key=lambda kv: kv[1].name):
            w.writerow([rule.name, ", ".join(sorted(rule.input_sig.tags)).lower(), rule.doc])
    with open(os.path.join(outdir, "supportedAggs.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["Aggregate", "InputTypes", "Notes"])
        for _, rule in sorted(AGG_RULES.items(), key=lambda kv: kv[1].name):
            w.writerow([rule.name, ", ".join(sorted(rule.input_sig.tags)).lower(), rule.doc])


def main() -> None:
    docs = os.path.join(ROOT, "docs")
    os.makedirs(docs, exist_ok=True)
    with open(os.path.join(docs, "configs.md"), "w") as f:
        f.write(gen_configs_md())
    with open(os.path.join(docs, "supported_ops.md"), "w") as f:
        f.write(gen_supported_ops())
    with open(os.path.join(docs, "metrics.md"), "w") as f:
        f.write(gen_metrics_md())
    gen_csvs(os.path.join(ROOT, "tools", "generated_files"))
    print("wrote docs/configs.md docs/supported_ops.md docs/metrics.md "
          "tools/generated_files/*.csv")


if __name__ == "__main__":
    main()
