"""Pallas TPU kernels for the serving hot path.

Each kernel has an XLA counterpart in ``smg_tpu/ops/attention.py``.  Which one
a compiled program uses is decided in one place, from the platform, the mesh
and the program's shapes: ``ModelRunner._resolve_attn_impl``,
``_attn_impl_for`` and ``_prefill_impl_for`` (``smg_tpu/engine/runner.py``).
"""
