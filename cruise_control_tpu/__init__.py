"""cruise-control-tpu: a TPU-native Kafka cluster balancer.

A brand-new framework with the capabilities of Kafka Cruise Control
(reference: cawright-rh/cruise-control), re-designed TPU-first:

- cluster state lives in dense JAX arrays (``model/``),
- goal scoring is a vmap'd kernel over thousands of candidate actions
  (``analyzer/goals/``),
- the rebalance search is a jitted fixed-point loop, shardable over a
  ``jax.sharding.Mesh`` (``analyzer/search.py``, ``parallel/``),
- monitoring, execution, anomaly detection and the REST surface are
  host-side async services around that solver core
  (``monitor/``, ``executor/``, ``detector/``, ``api/``).

Reference layer map: see SURVEY.md §1 (cruise-control/src/main/java/...).
"""

__version__ = "0.4.0"


def enable_persistent_compile_cache(cache_dir: str | None = None,
                                    min_compile_secs: float = 1.0) -> str:
    """Turn on XLA's persistent compilation cache; returns the directory
    in force. One rule decides where it lives:

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it at import, so the
      program sets NO directory in code and that one is used as is
      (``cache_dir`` is ignored) — the deployment places the cache.
    - unset: ``cache_dir`` when given (``solver.compile.cache.dir``),
      else ``<checkout>/.jax_cache`` next to this package (git-ignored).

    The directory is fixed, never derived from the host: the path is part
    of what a later process must reproduce to hit. Idempotent; safe after
    jax import, must run before the first jit execution to help it."""
    import os

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    cache_dir = cache_dir or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
