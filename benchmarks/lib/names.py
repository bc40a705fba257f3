"""What the trace calls the program's steps and kernels. The program
names them itself since PR 31: every ``pl.pallas_call`` has a ``name=``,
which the trace reducer (benchmarks/lib/trace.short_name) shows as
``tpu_custom_call:<name>``; autodiff wraps a kernel's name as
``jvp_<name>_``, and under ``shard_map`` it stays bare. The matches are
exact: another Mosaic kernel in the same step is not counted as one of
these (tests/test_program_names.py holds the program to the names).
"""

from __future__ import annotations

PAGED_ATTN_KERNELS = frozenset({"tpu_custom_call:paged_window_attention"})
FLASH_KERNELS = frozenset(
    f"tpu_custom_call:{wrapped}"
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    for wrapped in (name, f"jvp_{name}_"))


def is_decode_step(module_name: str) -> bool:
    """PagedDecoder._step_impl's program on the 'XLA Modules' line:
    'jit__step_impl(<fingerprint>)'."""
    return module_name.startswith("jit__step_impl")


def is_train_step(module_name: str) -> bool:
    """trainer._train_step's program: jit(step) or jit(sharded)."""
    return module_name.startswith(("jit_step", "jit_sharded"))


def is_paged_attn_kernel(op_name: str) -> bool:
    """ops/pallas_decode.py's ``paged_window_attention``, the decode step's
    attention over the page pools."""
    return op_name in PAGED_ATTN_KERNELS


def is_flash_kernel(op_name: str) -> bool:
    """The flash forward and backward kernels of ops/pallas_attention.py:
    ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, bare or inside
    autodiff's ``jvp_..._``."""
    return op_name in FLASH_KERNELS
