"""Slope-based device timing.

A single jitted call + block_until_ready carries a dispatch-and-sync
overhead that does not depend on its content, so absolute per-call
timings of small ops measure mostly that.  Instead every op is run K times
inside one jitted lax.scan for two different K and the device time per
iteration is the SLOPE between the two totals — call overhead cancels.
"""

from __future__ import annotations

import time

import numpy as np


def device_time_ms(make_scan_fn, k1=32, k2=288, samples=3):
    """make_scan_fn(K) -> jitted fn(seed) running the op K times.

    Returns per-iteration device ms via the slope (min-over-samples totals).
    """
    import jax

    f1, f2 = make_scan_fn(k1), make_scan_fn(k2)
    jax.block_until_ready(f1(0))
    jax.block_until_ready(f2(0))
    t1s, t2s = [], []
    for s in range(samples):
        t0 = time.perf_counter()
        jax.block_until_ready(f1(s + 1))
        t1s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f2(s + 1))
        t2s.append(time.perf_counter() - t0)
    return (min(t2s) - min(t1s)) / (k2 - k1) * 1000.0


def scan_op(body):
    """Wrap op body(seed_scalar)->array into make_scan_fn for device_time_ms."""
    import jax
    import jax.numpy as jnp

    def make(K):
        def fn(seed):
            def step(c, i):
                o = body(i + c)
                return jnp.sum(o.astype(jnp.float32)).astype(jnp.int32) % 3, None

            c, _ = jax.lax.scan(step, jnp.int32(seed), jnp.arange(K))
            return c

        return jax.jit(fn)

    return make
