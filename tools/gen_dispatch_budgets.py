"""Golden dispatch-budget + cost-signature generator (NDS probe).

Writes two artifacts under tests/golden_plans/:

- ``dispatch_budgets.json``: for every translated NDS query
  (tools/nds_probe.py QUERIES), the static per-batch device-dispatch
  budget of its CONVERTED plan as computed by
  ``analysis.plan_verify.dispatch_budget`` — narrow dispatches per
  batch, fusion groups, pipeline boundaries, exec census.
- ``cost_signatures.json``: the kernel cost auditor's per-query COST
  SIGNATURE (analysis/kernel_audit.py) for every NDS query — per
  kernel family: dispatches, audited entries/shapes, XLA flops and
  bytes accessed, input/output plane bytes — plus the
  ``KERNEL_PRIMITIVES`` roster, so CI catches a kernel that silently
  starts moving 2x the bytes even when wall time hides it.

The tables are the same tiny SF / seed the tier-1 NDS regression uses,
so the committed artifacts pin exactly the plans CI sees.

tests/test_analysis.py re-derives each budget and diffs it against the
budget file (``compare_budget``); tests/test_kernel_audit.py diffs a
cold 2-query prefix (tier-1) and the full set (@slow) against the
signature file (``kernel_audit.compare_signature``) — a regression
fails loudly with the changed dimension named per query.

DETERMINISM CONTRACT (the cost pass): signatures are reproducible only
under the exact replay this generator performs — a FRESH session and
freshly generated tables (the budgets pass leaks session state
otherwise), ``gen_tables(SF=0.002, seed=7)``, the compile cache AND
audit record table cleared together (``clear_for_cold_audit``), and
queries executed in sorted name order. Accounting is shape-complete
(every traced shape is audited), so within that replay the signatures
are thread-order and process independent (jax's executables are
released every RELEASE_EVERY queries — see the constant); two consecutive generator
runs must produce byte-identical cost_signatures —
``tools/audit_smoke.py`` gates exactly that. The generator ABORTS on
any audit finding (an unresolvable cost analysis or a dispatch of an
entry traced before the audit armed): a golden pin of an incompletely
audited run is void.

Run after any INTENDED plan- or kernel-shape change:

    python tools/gen_dispatch_budgets.py
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# mirror tests/conftest.py EXACTLY: the budgets pin the plans the tier-1
# suite converts, and plan shape depends on the device count (the
# single-device complete-agg path in overrides.py vs partial+exchange)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_enable_fast_math" not in _flags:
    _flags = (_flags + " --xla_cpu_enable_fast_math=false").strip()
os.environ["XLA_FLAGS"] = _flags

#: keep in lockstep with tests/test_nds_probe.py's fixture — the golden
#: budgets must pin the exact plans the tier-1 suite converts
SF = 0.002
SEED = 7

#: pinned EXPLICITLY (not left to the conf default): adaptive execution
#: changes plan shape (AdaptiveShuffledHashJoinExec in the census, the
#: measured cost pass replanning exchanges from history), so a golden
#: generated under a drifted default would silently pin different plans
#: than CI converts. Recorded in both artifact headers.
ADAPTIVE = "true"

#: part of the cost-pass recipe: drop jax's compiled executables
#: (jax.clear_caches) before every RELEASE_EVERY-th query. XLA:CPU under
#: jaxlib 0.9.0 holds ~24 memory mappings per live executable, and the
#: 98-query pass in ONE process runs into the kernel's default
#: vm.max_map_count (65530) near query 65 ("LLVM compilation error:
#: Cannot allocate memory"). A release changes no signature: keyed
#: entries tally per dispatch, and the audit credits a module kernel's
#: trace once per audited SHAPE, not per re-trace. The replay in
#: tests/test_kernel_audit.py releases at the same points.
RELEASE_EVERY = 20

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                   "tests", "golden_plans", "dispatch_budgets.json")
OUT_SIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "tests", "golden_plans", "cost_signatures.json")


def _load_nds():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "nds_probe.py")
    spec = importlib.util.spec_from_file_location("nds_probe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_budgets():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.analysis.plan_verify import (dispatch_budget,
                                                       verify_plan)
    from spark_rapids_tpu.sql.session import TpuSession

    nds = _load_nds()
    sess = TpuSession({"spark.rapids.sql.adaptive.enabled": ADAPTIVE})
    tables = nds.gen_tables(SF, seed=SEED)
    d = {name: sess.create_dataframe(t).cache()
         for name, t in tables.items()}
    budgets = {}
    for qn in sorted(nds.QUERIES):
        df = nds.QUERIES[qn](sess, d)
        exec_root, _meta = sess.prepare_execution(df.plan)
        verify_plan(exec_root)  # a golden pin of an ILLEGAL plan is void
        budgets[qn] = dispatch_budget(exec_root)
    return budgets


def build_cost_signatures(limit=None, queries=None):
    """The audited cost pass: execute every NDS query on a FRESH
    session with the kernel cost auditor armed, from a cold compile
    cache, in sorted name order (the determinism contract in the module
    docstring). Returns {query_name: signature}. Raises RuntimeError on
    any audit finding."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.analysis import kernel_audit as KA
    from spark_rapids_tpu.sql.session import TpuSession

    nds = _load_nds()
    # a fresh session AND fresh tables: the budgets pass (or any prior
    # work in this process) must not decide which query first-traces a
    # shared entry
    sess = TpuSession({"spark.rapids.obs.audit.enabled": "true",
                       "spark.rapids.sql.adaptive.enabled": ADAPTIVE})
    tables = nds.gen_tables(SF, seed=SEED)
    d = {name: sess.create_dataframe(t).cache()
         for name, t in tables.items()}
    KA.clear_for_cold_audit()
    names = sorted(queries if queries is not None else nds.QUERIES)
    if limit:
        names = names[:int(limit)]
    sigs = {}
    for i, qn in enumerate(names):
        if i and i % RELEASE_EVERY == 0:
            jax.clear_caches()
        df = nds.QUERIES[qn](sess, d)
        df.collect()
        sig = KA.query_signature(sess.last_audit())
        if sig is None:
            raise RuntimeError(f"{qn}: no audit summary (audit disarmed "
                               f"mid-pass?)")
        sigs[qn] = sig
    found = KA.findings()
    if found:
        raise RuntimeError(
            "audit findings void this golden run:\n  "
            + "\n  ".join(found[:20]))
    return sigs


def signature_doc(sigs) -> dict:
    from spark_rapids_tpu.analysis.kernel_audit import KERNEL_PRIMITIVES
    return {"_generator": "tools/gen_dispatch_budgets.py",
            "_sf": SF, "_seed": SEED, "_adaptive": ADAPTIVE,
            "_release_every": RELEASE_EVERY,
            "kernel_primitives": sorted(KERNEL_PRIMITIVES),
            "cost_signatures": sigs}


def dump_signatures(sigs, path) -> None:
    with open(path, "w") as f:
        json.dump(signature_doc(sigs), f, indent=1, sort_keys=True)
        f.write("\n")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    sig_only = "--signatures-only" in argv
    budgets_only = "--budgets-only" in argv
    limit = None
    out_sig = OUT_SIG
    if "--limit" in argv:
        limit = int(argv[argv.index("--limit") + 1])
    if "--out" in argv:
        out_sig = argv[argv.index("--out") + 1]
    if limit and os.path.abspath(out_sig) == os.path.abspath(OUT_SIG):
        # a partial pass must never overwrite the committed 98-query
        # golden: audit_smoke and the tier-1 prefix would then diff
        # against a truncated artifact
        print("error: --limit requires --out (refusing to overwrite "
              "the committed golden with a partial signature set)",
              file=sys.stderr)
        return 2
    if not sig_only:
        budgets = build_budgets()
        doc = {"_generator": "tools/gen_dispatch_budgets.py",
               "_sf": SF, "_seed": SEED, "_adaptive": ADAPTIVE,
               "budgets": budgets}
        with open(OUT, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        total = sum(b["narrow_dispatches_per_batch"]
                    for b in budgets.values())
        print(f"wrote {os.path.relpath(OUT)}: {len(budgets)} queries, "
              f"{total} narrow dispatches/batch total")
    if not budgets_only:
        if not sig_only:
            # process purity: the cost pass replays in a FRESH
            # interpreter so the committed golden comes from exactly
            # the process shape audit_smoke's determinism gate re-runs
            # (the budgets pass above must not be able to leak
            # process-global state into the signatures)
            import subprocess
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--signatures-only", "--out", out_sig]
            if limit:
                cmd += ["--limit", str(limit)]
            rc = subprocess.run(cmd).returncode
            if rc != 0:
                return rc
            return 0
        sigs = build_cost_signatures(limit=limit)
        dump_signatures(sigs, out_sig)
        nbytes = sum(c["bytes_accessed"] for s in sigs.values()
                     for c in s.values())
        print(f"wrote {os.path.relpath(out_sig)}: {len(sigs)} query cost "
              f"signatures, {nbytes / 1e9:.3f} GB audited bytes total")
    return 0


if __name__ == "__main__":
    sys.exit(main())
