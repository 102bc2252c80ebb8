"""Spreeze reproduction package.

Two global knobs live here (the one module allowed to write
``jax.config``):

``jax_threefry_partitionable`` is switched on at import. The
framework's whole design moves tensors between layouts (replicated
eager warmup, sharded megastep, shard_map replay kernels), and with the
legacy non-partitionable threefry the VALUES of ``jax.random`` draws
depend on how GSPMD partitions the generating computation — e.g.
constraining the training batch to ``P("batch")`` silently changes the
SAC action noise, so a kernel that merely pins a sharding would
"diverge" from its oracle by design. Partitionable threefry makes every
draw layout-invariant (it is also the modern jax default), at the cost
of a one-time change of the raw streams relative to the legacy impl —
all in-repo comparisons are path-vs-path within one process, so nothing
observable depends on the legacy bits.

The persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already uses it and nothing is set here. Otherwise the
cache lives at ``<checkout>/.jax_cache`` — a fixed path, because the
path is part of the cache key, and inside the checkout, so a run writes
nothing around it. Processes of one checkout (the megastep compiles in
seconds to a minute on a TPU) then share compiled programs.
"""
import os as _os

import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__)))), ".jax_cache"))
