"""const-hoist: module-level device arrays hoisted into jaxpr consts.

The exact hazard class the ops modules guard BY HAND COMMENT today
(`rowmin.py:36`, `rank.py:28`, `segment.py:51-52`): a module-level
``jnp.float32(...)`` / ``jnp.array(...)`` captured by a traced function
becomes a hoisted const of the ClosedJaxpr — an EXTRA EXECUTABLE
PARAMETER.  Two failure modes follow:

* this jaxlib's dispatch fastpath drops hoisted consts when sibling
  cfg-variant executables coexist (observed: "Execution supplied 57
  buffers but compiled program expected 58", see ops/engine.empty_acquire);
* evaluating the module const at import time initializes the backend
  before the process picks a platform (the TickOutput.seg_dropped
  comment documents the same trap).

The fix is always the same one-liner the comments prescribe: make the
module const a **numpy scalar/array** (`np.int32(...)`) — numpy consts
inline into the program as literals instead of riding as device buffers.
The AST tier cannot see this (both spellings are module-level
assignments); the jaxpr shows the const's concrete type.

Large numpy consts (> 64 KiB) get a WARNING: they bloat every executable
that closes over them and usually want to be explicit inputs.
"""

from __future__ import annotations

from typing import Iterable

from sentinel_tpu.analysis.framework import WARNING, Finding
from sentinel_tpu.analysis.jaxpr.framework import (
    JaxprPass,
    TracedEntry,
    walk_closed,
)

_BIG_NP_CONST_BYTES = 1 << 16


class ConstHoistPass(JaxprPass):
    name = "const-hoist"
    description = "no module-level device-array consts hoisted into jaxprs"

    def run(self, entry: TracedEntry) -> Iterable[Finding]:
        import jax
        import numpy as np

        seen = set()
        for closed in walk_closed(entry.closed_jaxpr):
            for c in getattr(closed, "consts", ()):
                if id(c) in seen:
                    continue
                seen.add(id(c))
                if isinstance(c, jax.Array):
                    yield self.finding(
                        entry,
                        f"device-array const {c.dtype}{tuple(c.shape)} hoisted "
                        "into the jaxpr — an extra executable parameter; the "
                        "dispatch fastpath drops hoisted consts when sibling "
                        "cfg-variant executables coexist, and evaluating it "
                        "at import initializes the backend early.  Spell the "
                        "module constant in numpy (np.int32(...) not "
                        "jnp.int32(...)) so it inlines as a literal "
                        "(see ops/rowmin.py:36)",
                    )
                    continue
                # a closed-over numpy array reaches the jaxpr wrapped in
                # jax's typed-literal holder (its ndarray sits in .val)
                c = np.asarray(getattr(c, "val", c))
                if c.nbytes > _BIG_NP_CONST_BYTES:
                    yield self.finding(
                        entry,
                        f"large numpy const {c.dtype}{tuple(c.shape)} "
                        f"({c.nbytes} bytes) baked into the jaxpr — bloats "
                        "every executable closing over it; pass it as an "
                        "explicit input instead",
                        severity=WARNING,
                    )
