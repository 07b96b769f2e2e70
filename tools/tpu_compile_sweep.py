"""Compile the engine's programs with the REAL TPU compiler, on a machine
with no chip.

`jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")` hands out v5e device descriptions from the
installed libtpu alone, and a jitted function traced against
ShapeDtypeStructs placed on one of them lowers and compiles through the
full TPU pipeline — the x64 rewriter and Mosaic included — without ever
touching hardware. CPU tests cannot see what those two refuse (a 64-bit
`bitcast_convert_type`, a Pallas kernel Mosaic cannot legalize); this
sweep can, before any chip time is spent.

How: the engine runs chip_smoke.py's sections on the CPU backend at
--rows, and every jitted callable that passes the compile choke point
(`compile_cache.get` for keyed stage bodies, `compile_cache.jit` for the
module kernels in ops/) is ALSO compiled for the v5e, once per argument
signature, with Pallas interpret mode forced off for that trace.
Compiling is not running: right answers on the device are chip_smoke.py's
job, on the chip. Sharded (shard_map) programs bake a mesh of CPU devices
and are skipped.

This is a CPU tool: it pins JAX_PLATFORMS=cpu and never needs the chip.

Usage: python tools/tpu_compile_sweep.py [--rows 200000]
           [--sections load,resident,scan,kernels]
Exit code 0 only if the TPU compiler took every program.
"""
import argparse
import contextlib
import functools
import json
import os
import sys
import time
import warnings

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

# the hook goes in BEFORE any ops/ module decorates its kernels
from spark_rapids_tpu.runtime import compile_cache as CC  # noqa: E402


class Sweep:
    def __init__(self):
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        self.device = topo.devices[0]
        self.sharding = SingleDeviceSharding(self.device)
        self.sweeping = False
        #: (name, signature, seconds, has-Mosaic-kernel, error-or-None)
        self.results = []

    def _spec(self, x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=self.sharding)
        return x

    def compile_for_tpu(self, name, body, jit_kwargs, args, kwargs):
        # a FRESH function object and a different trace context
        # (numpy_rank_promotion rides in jax's trace-cache key and
        # changes no semantics): neither this body nor any jitted kernel
        # it calls may reuse a jaxpr the CPU run traced with the Pallas
        # interpreter on
        fresh = jax.jit(functools.wraps(body)(
            lambda *a, **k: body(*a, **k)), **jit_kwargs)
        specs = jax.tree.map(self._spec, (args, kwargs))
        sig = str(jax.tree.map(
            lambda s: f"{s.dtype}{list(s.shape)}"
            if isinstance(s, jax.ShapeDtypeStruct) else repr(s), specs))
        t0 = time.perf_counter()
        err = None
        mosaic = False
        self.sweeping = True
        try:
            with warnings.catch_warnings(), \
                    jax.numpy_rank_promotion("warn"):
                warnings.simplefilter("ignore")
                lowered = fresh.trace(*specs[0], **specs[1]).lower()
                mosaic = "tpu_custom_call" in lowered.as_text()
                lowered.compile()
        except Exception as e:  # noqa: BLE001 - every refusal is a
            err = f"{type(e).__name__}: {e}"  # finding, reported below
        finally:
            self.sweeping = False
        dt = time.perf_counter() - t0
        self.results.append((name, sig, dt, mosaic, err))
        print(f"[sweep] {'REFUSED' if err else 'ok':7} {dt:6.1f}s "
              f"{'mosaic ' if mosaic else ''}{name}"
              + (f"\n        {err[:600]}" if err else ""),
              file=sys.stderr, flush=True)

    def jit(self, body, **jit_kwargs):
        """Stand-in for jax.jit at compile_cache's two sanctioned
        sites."""
        sweep = self
        jfn = jax.jit(body, **jit_kwargs)
        name = getattr(body, "__qualname__", repr(body))
        if getattr(body, "__module__", None):
            name = f"{body.__module__}.{name}"
        seen = set()

        class Swept:
            def __call__(self, *args, **kwargs):
                leaves = jax.tree.leaves((args, kwargs))
                if not sweep.sweeping and not any(
                        isinstance(x, jax.core.Tracer) for x in leaves):
                    devs = {d for x in leaves if isinstance(x, jax.Array)
                            for d in x.sharding.device_set}
                    # arrays by shape/dtype, statics by value; any other
                    # leaf by type (its repr may carry an address)
                    key = str(jax.tree.map(
                        lambda x: (x.shape, str(x.dtype))
                        if isinstance(x, jax.Array) else x
                        if isinstance(x, (int, float, bool, str, bytes,
                                          type(None)))
                        else type(x).__name__, (args, kwargs)))
                    if key not in seen and len(devs) <= 1:
                        seen.add(key)
                        sweep.compile_for_tpu(name, body, jit_kwargs,
                                              args, kwargs)
                return jfn(*args, **kwargs)

            def __getattr__(self, attr):
                return getattr(jfn, attr)

        return Swept()


class _JaxWithSweptJit:
    """`jax` as compile_cache sees it: jit swapped, the rest untouched."""

    def __init__(self, sweep):
        self.jit = sweep.jit

    def __getattr__(self, attr):
        return getattr(jax, attr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--sections", default="load,resident,scan,kernels")
    args = ap.parse_args()

    sweep = Sweep()
    CC.jax = _JaxWithSweptJit(sweep)
    from spark_rapids_tpu.ops import pallas_kernels as PK
    cpu_interpret = PK._interpret
    PK._interpret = lambda: cpu_interpret() and not sweep.sweeping

    import chip_smoke
    with contextlib.redirect_stdout(sys.stderr):  # ONE JSON on stdout
        rc = chip_smoke.main(["--allow-cpu", "--rows", str(args.rows),
                              "--sections", args.sections])
    refused = [r for r in sweep.results if r[4]]
    print(json.dumps({
        "target": f"{sweep.device.device_kind} (topology v5e:2x2, no chip)",
        "rows": args.rows, "programs": len(sweep.results),
        "programs_with_mosaic_kernels": sum(r[3] for r in sweep.results),
        "refused": len(refused),
        "tpu_compile_s": round(sum(r[2] for r in sweep.results), 1),
        "cpu_smoke_rc": rc,
        "refusals": [{"name": n, "signature": s[:400], "error": e[:1500]}
                     for n, s, _, _, e in refused]}, indent=1))
    return 1 if refused or rc else 0


if __name__ == "__main__":
    sys.exit(main())
