"""Global process configuration.

Replaces the reference's gflags tier (paddle/utils/Flags.cpp:18-81 — ~40
process flags like use_gpu, trainer_count, ports, trainer_id) with a single
typed config object. Device selection is `use_tpu` beside the reference's
`use_gpu`; on a machine without TPUs JAX's CPU backend plays the role the
reference's CPU-only build (paddle/cuda/include/stub/*) played: the universal
fake device every test can run on.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class GlobalConfig:
    # Device policy (reference: use_gpu flag, Flags.cpp:18)
    use_tpu: bool = False
    # Data-parallel width; reference: trainer_count (Flags.cpp:23). 0 = all devices.
    trainer_count: int = 1
    # Reference: trainer_id / num_gradient_servers for multi-host (Flags.cpp:55-60).
    process_index: int = 0
    process_count: int = 1
    # Numeric policy: parameters are kept f32; matmul/conv compute dtype.
    compute_dtype: str = "float32"
    # Reference: log_period (Flags.cpp:33)
    log_period: int = 100
    # Reference: seed flag for deterministic runs
    seed: int = 0
    # FPE-trap equivalent (TrainerMain.cpp:49): raise at the first NaN.
    debug_nans: bool = False
    # Pallas flash attention for tile-friendly shapes on TPU
    use_flash_attention: bool = True
    initialized: bool = False


_g = GlobalConfig()


def init(use_tpu: Optional[bool] = None, use_gpu: Optional[bool] = None,
         trainer_count: int = 1, seed: int = 0, compute_dtype: str = "float32",
         log_period: int = 100, debug_nans: bool = False,
         **kwargs) -> GlobalConfig:
    """Initialize the framework. Mirrors paddle.v2.init(use_gpu=..., trainer_count=...).

    `use_gpu` is accepted for source compatibility with v2 scripts and treated
    as a request for the accelerator backend (i.e. the TPU here). An explicit
    request that the process cannot meet raises: no run believes it is on the
    chip while it computes on the CPU.

    `debug_nans=True` is the FPE-trap discipline of the reference trainer
    (TrainerMain.cpp:49 feenableexcept(FE_INVALID|FE_DIVBYZERO|FE_OVERFLOW)):
    XLA re-runs any computation that produced a NaN un-jitted and raises at
    the exact primitive (jax_debug_nans), so a diverging run fails loudly at
    the source instead of training on garbage.
    """
    import jax

    # set AND clear: a later init(debug_nans=False) must un-latch the flag
    jax.config.update("jax_debug_nans", bool(debug_nans))
    _g.debug_nans = debug_nans
    if use_tpu is None:
        use_tpu = bool(use_gpu) if use_gpu is not None else None
    on_tpu = jax.default_backend() == "tpu"
    if use_tpu is None:
        use_tpu = on_tpu
    elif use_tpu and not on_tpu:
        raise RuntimeError(
            "init(use_tpu=True) but JAX's default backend is "
            f"{jax.default_backend()!r}: no TPU is attached to this "
            "process (leave use_tpu unset to take what is there)")
    _g.use_tpu = use_tpu
    _g.trainer_count = trainer_count if trainer_count > 0 else jax.local_device_count()
    _g.seed = seed
    _g.compute_dtype = compute_dtype
    _g.log_period = log_period
    _g.process_index = jax.process_index()
    _g.process_count = jax.process_count()
    _g.initialized = True
    return _g


def global_config() -> GlobalConfig:
    return _g
