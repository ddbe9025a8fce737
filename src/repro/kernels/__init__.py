"""Pallas TPU kernels for the serving hot paths (validated in interpret
mode on CPU; compiled through Mosaic on real TPUs):

* flash_attention — prefill attention (causal / sliding-window / ALiBi /
  chunked-prefill ``q_start`` / GQA / cross)
* decode_attention — single-token attention over long KV caches (per-row
  ``pos``, window, ALiBi, cross ``kv_len``, GQA + MLA faithful scale)
* wkv6 — RWKV6 chunked recurrence (carried state in/out)
* ssd — Mamba2 state-space-dual chunked scan (carried state in/out)

Each wrapper ships a ``*_unsupported(**features) -> Optional[str]``
predicate naming the feature it cannot serve (the serving backend layer's
XLA-fallback dispatch test); calling a wrapper with an unsupported feature
raises ``ValueError`` instead of returning wrong numbers.  Shared runtime
knobs (interpret mode off-TPU, backend-name validation, the counted
per-call kernel-or-fallback decision) live in ``repro.kernels.runtime``.
"""
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref,
                                            decode_attention_unsupported)
from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_unsupported)
from repro.kernels.runtime import BACKENDS, default_interpret, resolve_backend
from repro.kernels.ssd import ssd, ssd_ref, ssd_unsupported
from repro.kernels.wkv6 import wkv6, wkv6_ref, wkv6_unsupported

__all__ = ["BACKENDS", "attention_ref", "decode_attention",
           "decode_attention_ref", "decode_attention_unsupported",
           "default_interpret", "flash_attention",
           "flash_attention_unsupported", "resolve_backend", "ssd",
           "ssd_ref", "ssd_unsupported", "wkv6", "wkv6_ref",
           "wkv6_unsupported"]
