"""The engine's own per-query phase account, as the per-layer readers
take it: the records `spark_rapids_tpu.runtime.obs.recent_queries()` holds
for the queries of the traced passes, the same passes the device metrics
come from (the ring outlives the session).

The records are picked by time, not by count: the traced passes are the
last queries the engine ran, so they are the records that start within the
trace's own window (`run.trace["window_s"]`, first pass to last) before the
newest record's end; `t0_ns` and `wall_ns` are on one monotonic clock. The
pick stands only if it is exactly passes x queries a pass long, numbered
consecutively and every query ok: any other top-level action in the window
changes the count, and the readers then say nothing rather than average
over the wrong queries. None too where there is no trace or no ring (a
program without the account, or spark.rapids.obs.enabled off).
"""

#: the window is measured on the profiler's clock around the passes, the
#: records inside them on the host's: room for the difference
SLACK = 1e-3


def records(run):
    if not run.trace or not run.queries_per_pass:
        return None
    try:
        from spark_rapids_tpu.runtime import obs
        recs = obs.recent_queries()
        n = run.trace["passes"] * run.queries_per_pass
        newest = recs[-1]
        start = newest["t0_ns"] + newest["wall_ns"] \
            - run.trace["window_s"] * (1 + SLACK) * 1e9
        recs = [r for r in recs if r["t0_ns"] >= start]
        whole = len(recs) == n and all(
            r["status"] == "ok" and r["seq"] == recs[0]["seq"] + i
            for i, r in enumerate(recs))
    except (ImportError, AttributeError, LookupError, TypeError):
        return None
    return recs if whole else None


def mean_of(run, value, scale=1.0):
    """Mean over the traced queries of `value(record)`, times `scale`;
    None where there are no records or a record lacks what `value` reads."""
    recs = records(run)
    if not recs:
        return None
    try:
        return scale * sum(value(r) for r in recs) / len(recs)
    except (KeyError, TypeError):
        return None
