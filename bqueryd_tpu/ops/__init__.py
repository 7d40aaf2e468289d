"""JAX columnar kernels (the compute role bquery's Cython kernels play in the
reference, used at reference bqueryd/worker.py:291-323).

Importing this package enables JAX 64-bit mode: the north-star acceptance
criterion is bit-for-bit int64 aggregates, and without ``jax_enable_x64``
int64 inputs silently degrade to int32.  Control-plane modules never import
this package, so pure controller/downloader processes stay JAX-free.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)


def _configure_compile_cache():
    """Persistent compilation cache: first-query-per-shape XLA compiles
    (seconds to minutes per program on a TPU) survive process restarts.
    Multi-process safe (atomic renames); every (ops, dtypes,
    n_groups-bucket) signature a worker has ever served warms the next
    restart.

    The directory is placed from OUTSIDE: ``JAX_COMPILATION_CACHE_DIR``,
    which JAX reads by itself — then no directory is set here.  Unset, the
    cache lives at one fixed path inside the checkout (``<repo>/.jax_cache``,
    resolved from ``__file__``): the path is part of the cache key, so a
    directory that moves with ``$HOME``, a pid or a temp name never hits.
    ``BQUERYD_TPU_COMPILE_CACHE=0`` turns the cache off (tests/conftest.py
    pins it so CPU test runs persist no XLA:CPU AOT artifacts, which log
    machine-feature-mismatch errors when reloaded on a different CPU)."""
    if os.environ.get("BQUERYD_TPU_COMPILE_CACHE", "1") == "0":
        # off means off even when the machine exports a cache directory
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(repo, ".jax_cache")
        )
    # cache every compile (the default 1 s floor would skip most of the
    # small per-shape programs whose aggregate warmup this kills)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")


_configure_compile_cache()


_distributed_initialized = False


def maybe_init_distributed(logger=None):
    """Join a multi-host JAX job when configured; no-op otherwise.

    Set ``BQUERYD_TPU_DIST_COORDINATOR=host:port`` on every host of a pod
    slice (plus ``BQUERYD_TPU_DIST_NPROCS`` / ``BQUERYD_TPU_DIST_PROC_ID``
    off-TPU, where they can't be inferred) and the calc worker becomes one
    process of a single multi-host JAX runtime: ``jax.devices()`` spans the
    slice, the mesh executor's 1-D shard mesh covers every chip, and the
    ``psum`` merge rides ICI within a host and DCN across hosts — the
    framework's answer to the reference's one-process-per-core scaling
    (reference misc/supervisor.conf:19-20).

    Must run before the first JAX backend touch; the worker calls it at
    construction time."""
    global _distributed_initialized
    coordinator = os.environ.get("BQUERYD_TPU_DIST_COORDINATOR")
    if not coordinator or _distributed_initialized:
        return False
    kwargs = {"coordinator_address": coordinator}
    if os.environ.get("BQUERYD_TPU_DIST_NPROCS"):
        kwargs["num_processes"] = int(os.environ["BQUERYD_TPU_DIST_NPROCS"])
    if os.environ.get("BQUERYD_TPU_DIST_PROC_ID"):
        kwargs["process_id"] = int(os.environ["BQUERYD_TPU_DIST_PROC_ID"])
    jax.distributed.initialize(**kwargs)
    _distributed_initialized = True
    if logger is not None:
        logger.info(
            "joined multi-host JAX job: process %d/%d, %d/%d devices local",
            jax.process_index(), jax.process_count(),
            len(jax.local_devices()), len(jax.devices()),
        )
    return True


from bqueryd_tpu.ops.factorize import (  # noqa: E402
    MAX_COMPOSITE,
    CompositeOverflow,
    factorize,
    factorize_device,
    pack_codes,
    total_cardinality,
    unpack_codes,
)
from bqueryd_tpu.ops.groupby import (  # noqa: E402
    AGG_OPS,
    MERGEABLE_OPS,
    bucketize_partials,
    bundle_partial_tables,
    combine_partials,
    expand_mask_by_group,
    finalize,
    float_sum_route,
    groupby_aggregate,
    groupby_count_distinct,
    groupby_sorted_count_distinct,
    host_partial_tables,
    host_sorted_count_distinct,
    kernel_route,
    partial_tables,
    partial_tables_bucketized,
    program_bucket,
)
from bqueryd_tpu.ops.predicates import (  # noqa: E402
    WHERE_OPS,
    build_mask,
    chunk_pruned_table,
    chunk_selection,
    shard_can_match,
    term_mask,
    translate_value,
)

__all__ = [
    "CompositeOverflow",
    "MAX_COMPOSITE",
    "factorize",
    "factorize_device",
    "pack_codes",
    "unpack_codes",
    "total_cardinality",
    "AGG_OPS",
    "MERGEABLE_OPS",
    "groupby_aggregate",
    "groupby_count_distinct",
    "groupby_sorted_count_distinct",
    "expand_mask_by_group",
    "host_partial_tables",
    "host_sorted_count_distinct",
    "float_sum_route",
    "kernel_route",
    "partial_tables",
    "partial_tables_bucketized",
    "program_bucket",
    "bucketize_partials",
    "bundle_partial_tables",
    "combine_partials",
    "finalize",
    "WHERE_OPS",
    "build_mask",
    "chunk_pruned_table",
    "chunk_selection",
    "shard_can_match",
    "term_mask",
    "translate_value",
]
