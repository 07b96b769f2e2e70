"""Whole-operator fusion: one jitted XLA computation per operator stage.

Reference parity/divergence: the reference calls one cuDF kernel per
primitive (a gather here, a hash there) — cheap when the device is on the
local PCIe bus. Every eager XLA dispatch has a fixed host-side cost,
so this framework fuses an ENTIRE operator (expression eval
+ filter-compact, or expression eval + sort + segmented aggregation) into
a single jit'd function over ColumnarBatch pytrees. XLA then fuses across
the whole stage; the host issues exactly one call per operator per batch.

Whole-STAGE vertical fusion (exec/stage_fusion.py) goes one level up:
linear chains of narrow operators expose their traced bodies as StageBody
records here and compose into one entry, so the host issues one call per
PIPELINE STAGE per batch.

The cache is keyed by a semantic fingerprint (expression fingerprints +
operator shape); jax.jit's own signature cache handles layout/capacity
variation beneath each entry — and runtime/shapes.py guarantees those
capacities come from a small bucket set, so the variation is bounded.
Storage, stats and first-call compile attribution live in the sanctioned
compile choke point (runtime/compile_cache.py); this module remains the
per-batch DISPATCH choke point where the failure-domain hooks hang.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

from spark_rapids_tpu.runtime import compile_cache as _cc
from spark_rapids_tpu.runtime import faults as _faults
from spark_rapids_tpu.runtime import lifecycle as _lc
from spark_rapids_tpu.runtime import watchdog as _watchdog
from spark_rapids_tpu.runtime.obs import phases as _ph

#: test/diagnostic hook called with the fuse key once per device dispatch
#: issued through fused() (the dispatch-budget regression harness; see
#: tests/test_stage_fusion.py). None in production — the wrapper costs one
#: attribute read per call.
_DISPATCH_HOOK: Optional[Callable[[Tuple], None]] = None


def set_dispatch_hook(hook: Optional[Callable[[Tuple], None]]) -> None:
    global _DISPATCH_HOOK
    _DISPATCH_HOOK = hook


def notify_dispatch(key: Tuple) -> None:
    """Report a device dispatch issued outside fused() (compiled.run_stage)
    to the phase account's counter and the budget hook."""
    _ph.keyed_dispatches += 1
    if _DISPATCH_HOOK is not None:
        _DISPATCH_HOOK(key)


def fused(key: Tuple, builder: Callable[[], Callable]) -> Callable:
    # key[0] names the operator family ("hash_exchange_compact",
    # "stage", ...): it doubles as the compile cache's exec-class so
    # hit/miss stats and warmup coverage group by operator kind. The
    # cache owns storage, conf fingerprinting, and first-call compile
    # attribution (7-11s first-run vs 0.6s steady on NDS).
    exec_class = key[0] if key and isinstance(key[0], str) else "fuse"
    fn = _cc.get(exec_class, key, builder)
    # fused() is THE per-batch device-dispatch choke point, so it is
    # also where the failure-domain hooks live: the device.dispatch
    # fault site, the dispatch watchdog's in-flight registration, and
    # the cooperative cancellation checkpoint. All gates are module-
    # global reads; with nothing armed AND no query lifecycle in flight
    # the raw jitted function returns and a dispatch costs exactly what
    # it did before any of this machinery existed. With only a cancel
    # token live (every real query), the wrapper is the checkpoint
    # alone — one token-table read per dispatch.
    if _DISPATCH_HOOK is None and not _faults.armed("device.dispatch") \
            and not _watchdog.active():
        if not _lc.active():
            return fn

        def checked(*args, **kwargs):
            _lc.check_current()
            _ph.keyed_dispatches += 1  # the query's phase account reads it
            return fn(*args, **kwargs)

        return checked

    def counted(*args, **kwargs):
        _lc.check_current()
        notify_dispatch(key)
        with _watchdog.guard("device.dispatch"):
            # inside the guard so a wedge-kind fault is exactly what the
            # watchdog exists to detect
            _faults.site("device.dispatch")
            return fn(*args, **kwargs)

    return counted


def clear_cache() -> None:
    """Drop every cached fused entry (tests/profiling; delegates to the
    process-wide compile cache, which also drops the run_stage and
    absorbed-agg entries)."""
    _cc.clear()


class StageBody:
    """One fusable operator's traced body, separated from its driver loop
    so exec/stage_fusion.py can compose several into ONE jitted entry.

    builder() returns the uniform traced function
        fn(batch, pid, carry) -> (batch, errors_dict, carry)
    where `pid` is the traced partition id and `carry` is the operator's
    per-partition loop state (ProjectExec's row_base, LimitExec's
    remaining budget; a constant zero scalar for carry-free operators).
    Builders MUST capture only expression-level state — never the exec
    node, whose child tree can pin HBM-resident batches in the process-
    global fuse cache.

    bounds_map maps host-side column-stat bounds (ColumnVector.bounds,
    NOT pytree leaves) across the operator: in_bounds per input column ->
    bounds per output column.
    """

    __slots__ = ("key", "builder", "carry_init", "bounds_map", "has_carry",
                 "exhausts", "name")

    def __init__(self, key: Tuple, builder: Callable[[], Callable],
                 carry_init: Optional[Callable] = None,
                 bounds_map: Optional[Callable] = None,
                 has_carry: bool = False, exhausts: bool = False,
                 name: str = ""):
        self.key = key
        self.builder = builder
        self.carry_init = carry_init
        self.bounds_map = bounds_map
        self.has_carry = has_carry
        #: carry == 0 means every later batch is all-dead (LimitExec's
        #: remaining budget): the fused driver may stop consuming input
        self.exhausts = exhausts
        self.name = name

    def init_carry(self):
        import jax.numpy as jnp
        if self.carry_init is None:
            return jnp.int64(0)
        return self.carry_init()
