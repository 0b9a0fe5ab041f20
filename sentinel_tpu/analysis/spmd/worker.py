"""Forced-topology worker: lower the entry points, report the HLO facts.

Runs ONLY as ``python -m sentinel_tpu.analysis.spmd.worker`` in a child
process whose env the runner prepared with
``meshspec.force_cpu_mesh_env`` — booting the virtual n-device CPU
platform in the parent would freeze its jax topology for the rest of the
process (the same reason ``__graft_entry__.dryrun_multichip`` re-execs).

Protocol: one JSON report on the LAST stdout line; everything else
(jax warnings, progress) goes to stderr.  A nonzero exit or unparsable
report is surfaced by the runner as a loud analyzer ERROR, never as a
silently-empty tier.
"""

from __future__ import annotations

import json
import sys

from sentinel_tpu.parallel.meshspec import mesh_spec


def build_report() -> dict:
    import jax

    from sentinel_tpu.analysis import REPO_ROOT
    from sentinel_tpu.analysis.spmd.entrypoints import sharded_jobs
    from sentinel_tpu.analysis.spmd.framework import parse_hlo_collectives

    spec = mesh_spec()
    entries = []
    for name, fn, args in sharded_jobs():
        # one trace serves jaxpr (consts) and lowering (partitioned HLO)
        t = fn.trace(*args)
        consts = [
            {
                "dtype": str(getattr(c, "dtype", "?")),
                "shape": list(getattr(c, "shape", ())),
                "nbytes": int(getattr(c, "nbytes", 0)),
            }
            for c in t.jaxpr.consts
        ]
        hlo = t.lower().compile().as_text()
        colls = parse_hlo_collectives(hlo, REPO_ROOT)
        entries.append(
            {
                "name": name,
                "consts": consts,
                "collectives": [
                    {
                        "kind": c.kind,
                        "dtype": c.dtype,
                        "shape": list(c.shape),
                        "source": c.source,
                        "line": c.line,
                    }
                    for c in colls
                ],
            }
        )
        print(f"spmd-worker: {name}: {len(colls)} collective(s)", file=sys.stderr)
    return {
        "jax_version": jax.__version__,
        "n_devices": spec.n_devices,
        "axis": spec.axis,
        "entries": entries,
    }


def main() -> int:
    # The runner prepared the env (JAX_PLATFORMS=cpu + the forced device
    # count); verify the topology took before lowering anything.
    import jax

    spec = mesh_spec()
    if jax.default_backend() != "cpu":
        print(
            f"spmd-worker: backend {jax.default_backend()!r} != 'cpu' "
            "(platform forcing leaked through)",
            file=sys.stderr,
        )
        return 3
    n = len(jax.devices())
    if n != spec.n_devices:
        print(
            f"spmd-worker: {n} device(s) != forced {spec.n_devices} "
            "(xla_force_host_platform_device_count did not apply)",
            file=sys.stderr,
        )
        return 3
    report = build_report()
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
