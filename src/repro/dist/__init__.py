"""Distribution layer: mesh sharding policies + compressed collectives.

Three modules, split by concern:

* ``context``     — ambient (mesh, policy) context; models annotate tensors
                    with logical kind names via ``hint(x, kind)`` and the
                    active policy decides the physical ``PartitionSpec``.
* ``sharding``    — ``Policy`` + per-pytree PartitionSpec builders for params,
                    optimizer state, quantized embedding tables, batches and
                    decode caches.
* ``collectives`` — SR-quantized (int8) gradient all-reduce built on
                    ``repro.core.quant`` — the paper's stochastic-rounding
                    quantizer applied to communication.

Importing this package switches jax to *partitionable* threefry: with the
legacy (non-partitionable) PRNG the random bits depend on the output
sharding, so a mesh-sharded ``init_state`` would not reproduce the
single-device initialization.  Partitionable threefry makes
every ``jax.random`` draw sharding-invariant — the foundation of the
``sharded loss == single-device loss`` contract (tests/test_distribution.py).
"""
import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)

from repro.dist import collectives, context, sharding  # noqa: E402,F401
