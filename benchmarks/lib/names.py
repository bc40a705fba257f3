"""What the trace calls the program's steps and kernels TODAY. The
program gives its kernels no stable name yet (``pl.pallas_call`` without
``name=``, no ``jax.named_scope`` on the steps): these matches were read by
hand from a trace on the v5e and are the one place to change when the
tracing issue names them (PERF.md, Open questions).
"""

from __future__ import annotations


def is_decode_step(module_name: str) -> bool:
    """PagedDecoder._step_impl's program on the 'XLA Modules' line:
    'jit__step_impl(<fingerprint>)'."""
    return module_name.startswith("jit__step_impl")


def is_train_step(module_name: str) -> bool:
    """trainer._train_step's program: jit(step) or jit(sharded)."""
    return module_name.startswith(("jit_step", "jit_sharded"))


def is_paged_attn_kernel(op_name: str) -> bool:
    """The step's custom-call events: _paged_window_kernel is the only
    Mosaic kernel in the serving step."""
    return op_name.startswith("tpu_custom_call:")


def is_flash_kernel(op_name: str) -> bool:
    """The flash forward and backward kernels of ops/pallas_attention.py:
    the only custom calls in the train step."""
    return op_name.startswith("tpu_custom_call:")
