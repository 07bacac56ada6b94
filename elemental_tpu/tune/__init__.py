"""Autotuning subsystem (ISSUE 4): pick driver knobs per problem instead
of per call site.

Four layers, consulted in order by a driver that receives ``'auto'``:

  :mod:`.knobs`       what is tunable and which configs are legal
  :mod:`.cache`       persistent ``tuning_cache/v1`` measured winners
                      (``$ELEMENTAL_TPU_TUNE_CACHE`` overrides the dir)
  :mod:`.cost_model`  analytic scoring -- abstract driver traces (ring-model
                      collective bytes) + an MXU-roofline flop term; works
                      cold on CPU with no device execution
  :mod:`.policy`      resolution: explicit wins > cache > cost model; also
                      the canonical :func:`blocksize_policy`

:mod:`.measure` (imported lazily; it compiles and runs on the real
backend) times candidates in one process and records winners.  CLI:
``python -m perf.tune {search,show,clear,explain}``.
"""
from .knobs import (DEFAULT_CROSSOVER, GEMM_ALGS, NB_LADDER, OPS,
                    TuneContext, candidate_configs, nb_candidates, op_names)
from .cache import (SCHEMA as CACHE_SCHEMA, ENV_DIR as CACHE_ENV_DIR,
                    CacheKey, cache_dir, clear as clear_cache,
                    entries as cache_entries, load as cache_load,
                    make_key, save as cache_save, scan as cache_scan,
                    shape_bucket)
from .policy import (Resolution, blocksize_policy, clear_memo, explain,
                     is_auto, resolve, resolve_knobs, wants_auto)

__all__ = [
    "DEFAULT_CROSSOVER", "GEMM_ALGS", "NB_LADDER", "OPS", "TuneContext",
    "candidate_configs", "nb_candidates", "op_names",
    "CACHE_SCHEMA", "CACHE_ENV_DIR", "CacheKey", "cache_dir", "clear_cache",
    "cache_entries", "cache_load", "make_key", "cache_save", "cache_scan",
    "shape_bucket",
    "Resolution", "blocksize_policy", "clear_memo", "explain", "is_auto",
    "resolve", "resolve_knobs", "wants_auto",
]
