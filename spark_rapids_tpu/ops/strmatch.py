"""Literal-run string matching over flat string planes.

`s LIKE 'P%M1%M2...%S'` with literal runs (no `_`): s starts with P, ends
with S, and M1, M2, ... occur in that order between them without
overlapping; taking the leftmost occurrence of each run in turn decides it.
`startswith`, `endswith` and `contains` are the cases with one run.

The work is done by passes over the whole byte plane and ONE read a row
(two with a suffix), never a step a byte position of the longest row
(expr/regex.nfa_eval gathers the batch once a position) and never a search
for each byte's row. On a v5e a read of 8.4 M rows out of a 512 MiB plane
costs 123 ms, a run's planes 23 ms (PERF.md, PR 36), so the rows read once:

- a run's occurrences are a boolean plane over the bytes: one shifted
  compare a byte of the run, fused into one pass;
- the runs that float between two `%` are chained in the byte plane, last
  run first: the plane of run i holds, at every byte, the distance from
  there to the END of the leftmost chain Mi, Mi+1, ... that starts at or
  after it. At an occurrence of Mi that is its length plus the next run's
  plane read just behind it (a slice, the shift is static); everywhere
  else the nearest such occurrence ahead, by log2(width) doubling steps,
  each an elementwise minimum of the plane and itself shifted. `width`
  bounds the longest row (ColumnVector.str_width), so a chain that would
  end further off than a row is long reads as none;
- a row reads the first run's plane at its start (behind the prefix, whose
  own occurrence plane is folded in) and matches if the chain ends before
  its own end less the suffix. Rows are never told apart in the byte
  plane: a chain that straddles two rows ends past the first row's end,
  and is refused there.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.runtime import compile_cache as _cc


def _doubling(width: int) -> list:
    """Shifts whose doubling window reaches past `width`: 1, 2, 4, ..."""
    steps, step = [], 1
    while step <= width:
        steps.append(step)
        step *= 2
    return steps


def run_hits(padded: jax.Array, run: bytes, n: int) -> jax.Array:
    """bool[n]: `run` occurs at this byte of the plane (rows not yet
    respected). `padded` is the plane with at least len(run) - 1 more
    bytes behind it, so every shifted view is a slice."""
    hit = None
    for k, b in enumerate(run):
        eq = padded[k:k + n] == np.uint8(b)
        hit = eq if hit is None else hit & eq
    return hit


def _plus(d: jax.Array, k: int, none: int) -> jax.Array:
    """d + k, staying `none` (and becoming it past the dtype's room)."""
    return jnp.where(d >= none - k, none, d + k).astype(d.dtype)


def nearest_ahead(d: jax.Array, width: int, none: int) -> jax.Array:
    """At every byte the least of d[p + j] + j over j in 0..width (at
    least): the plane comes out shorter than `d` by the sum of the
    doubling steps, each of which reads the plane shifted as a slice and
    drops the tail that has nothing behind it."""
    for step in _doubling(width):
        d = jnp.minimum(d[:-step], _plus(d[step:], step, none))
    return d


def match_runs(offsets: jax.Array, raw: jax.Array, width: Optional[int],
               prefix: bytes, middles: Sequence[bytes], suffix: bytes
               ) -> jax.Array:
    """bool[rows] over a flat string column's planes (module docstring).
    `width` is a host-side bound on the longest row, or None: the planes'
    own size then bounds it."""
    if isinstance(raw, jax.core.Tracer):
        _cc.note_traced("like_plane_traced")
    nb = int(raw.shape[0])
    start, end = offsets[:-1].astype(jnp.int32), offsets[1:].astype(jnp.int32)
    middles = [m for m in middles if m]
    runs = [prefix, suffix, *middles]
    ok = (end - start) >= sum(len(r) for r in runs)
    if width is None:
        width = nb
    if width < 255 and all(len(r) < 255 for r in runs):
        dt, none = jnp.uint8, 255   # a chain longer than a row is none
    else:
        dt, none = jnp.int32, 1 << 30
    # how much of run i's plane the run before it (or the rows) will read:
    # the rows read nb bytes from behind the prefix; each run reads the
    # next one's plane just behind its own occurrences, over its own
    # doubling steps' room
    room = sum(_doubling(width))
    needs, need = [], nb + len(prefix)
    for m in middles:
        needs.append(need)
        need += room + len(m)
    # the plane once more with zeros behind it for every shifted view: a
    # chain that runs into them ends past every row, and a row refuses it
    padded = jnp.concatenate([raw, jnp.zeros(
        max(need, nb + len(suffix)) - nb, raw.dtype)])

    def at(plane, pos):
        return plane[jnp.clip(pos, 0, nb - 1)]

    chain = None    # to the end of the leftmost Mi, Mi+1, ... ahead
    for m, need in reversed(list(zip(middles, needs))):
        hit = run_hits(padded, m, need + room)
        behind = jnp.asarray(len(m), dt) if chain is None else _plus(
            chain[len(m):len(m) + need + room], len(m), none)
        chain = nearest_ahead(jnp.where(hit, behind, none).astype(dt),
                              width, none)
    if prefix:
        starts = run_hits(padded, prefix, nb)
        if chain is None:
            ok = ok & at(starts, start)
        else:   # the prefix here, then the chain from just behind it
            chain = jnp.where(starts, _plus(
                chain[len(prefix):len(prefix) + nb], len(prefix), none), none)
    if suffix:
        ok = ok & at(run_hits(padded, suffix, nb), end - len(suffix))
    if chain is not None:
        reach = at(chain, start).astype(jnp.int32)
        ok = ok & (reach < none) & (start + reach <= end - len(suffix))
    return ok
