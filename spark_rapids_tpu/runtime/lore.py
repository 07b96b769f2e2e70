"""LORE-analog: per-operator batch dump & local replay.

Reference parity: lore/GpuLore.scala (§5.1 — tag operators with IDs at
plan time, dump an operator's input batches + plan meta to disk, re-run
just that operator locally). Enabled by spark.rapids.sql.lore.dumpPath:
every exec node gets a lore id; its INPUT batches (= each child's output)
are dumped as parquet under <dir>/loreId=<id>/input<k>/part<p>/, with the
plan description in plan.txt. `replay(dir, lore_id)` rebuilds the exec
from the recorded plan subtree and re-executes it over the dumped inputs.
"""
from __future__ import annotations

import glob
import os
from typing import Iterator, List

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.columnar.batch import ColumnarBatch, from_arrow, to_arrow


class _DumpedChild:
    """Stands in for an exec child during replay: streams dumped batches."""

    def __init__(self, path: str, schema, nparts: int):
        self.path = path
        self.schema = schema
        self.children = []
        self.num_partitions = nparts

    def execute_partition(self, ctx, pidx) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.io import read_parquet_file
        for f in sorted(glob.glob(os.path.join(self.path, f"part{pidx}",
                                               "*.parquet"))):
            # file-scoped read: the dataset API would grow a phantom
            # loreId partition column from the dump path's k=v segment
            yield from_arrow(read_parquet_file(f))


class LoreDumper:
    """Installed by convert_plan when the dump path is set: walks the exec
    tree, assigns ids, and wraps each node's children so the batches
    flowing INTO every operator are recorded."""

    def __init__(self, root_dir: str):
        self.root_dir = root_dir
        self._next_id = 0

    def install(self, exec_root) -> None:
        self._walk(exec_root)

    def _walk(self, node) -> None:
        lore_id = self._next_id
        self._next_id += 1
        node.lore_id = lore_id
        d = os.path.join(self.root_dir, f"loreId={lore_id}")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "plan.txt"), "w") as f:
            # the id rides in the dump itself (not just the dir name) so a
            # hot span found in a trace — exec spans carry lore_id in their
            # args — maps straight to `lore.replay(dir, <loreId>, plan)`
            f.write(f"loreId={lore_id} exec={type(node).__name__}\n")
            f.write(node.tree_string())
        for i, child in enumerate(node.children):
            self._wrap_child(node, i, child, d)
            self._walk(child)

    def _wrap_child(self, parent, idx, child, parent_dir) -> None:
        inner = child.execute_partition
        names = child.schema.names
        dump_dir = os.path.join(parent_dir, f"input{idx}")

        def wrapped(ctx, pidx, _inner=inner, _names=names, _dir=dump_dir):
            seq = 0
            pdir = os.path.join(_dir, f"part{pidx}")
            os.makedirs(pdir, exist_ok=True)
            for batch in _inner(ctx, pidx):
                pq.write_table(to_arrow(batch, _names, "host"),
                               os.path.join(pdir, f"batch{seq:04d}.parquet"))
                seq += 1
                yield batch

        child.execute_partition = wrapped


def replay(root_dir: str, lore_id: int, plan, conf=None) -> pa.Table:
    """Re-run ONE operator over its dumped inputs. `plan` is the original
    logical plan (the lore ids follow the same conversion order), so the
    exec subtree is rebuilt exactly as planned; its children are replaced
    with dumped-batch streams (reference lore/replay.scala restoreGpuExec)."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.config import RapidsConf, set_session_conf
    from spark_rapids_tpu.plan.overrides import convert_plan
    from spark_rapids_tpu.runtime.task import TaskContext
    conf = conf or RapidsConf()
    if conf.get(C.LORE_DUMP_DIR):
        # replaying with the DUMPING conf would install a fresh dumper and
        # overwrite the recording being read; strip the key
        overrides = dict(conf._values)
        overrides.pop(C.LORE_DUMP_DIR.key, None)
        conf = RapidsConf(overrides)
    set_session_conf(conf)
    exec_root, _ = convert_plan(plan, conf)
    target = _find(exec_root, lore_id, counter=[0])
    if target is None:
        raise KeyError(f"no exec with lore id {lore_id}")
    d = os.path.join(root_dir, f"loreId={lore_id}")
    for i, child in enumerate(list(target.children)):
        ipath = os.path.join(d, f"input{i}")
        parts = len(glob.glob(os.path.join(ipath, "part*")))
        target.children[i] = _DumpedChild(ipath, child.schema, max(parts, 1))
    names = target.schema.names
    tables: List[pa.Table] = []
    for p in range(target.num_partitions):
        with TaskContext(partition_id=p) as ctx:
            for batch in target.execute_partition(ctx, p):
                tables.append(to_arrow(batch, names))
    return pa.concat_tables(tables) if tables else None


def _find(node, lore_id: int, counter) -> object:
    my_id = counter[0]
    counter[0] += 1
    if my_id == lore_id:
        return node
    for c in node.children:
        found = _find(c, lore_id, counter)
        if found is not None:
            return found
    return None
