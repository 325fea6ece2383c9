"""Pallas TPU kernels — the mx.kernels library.

The reference's hand-written CUDA/cuDNN kernels (SURVEY.md §2.1) map to
XLA codegen for almost everything; the exceptions live here as Pallas
kernels targeting the hot paths where mx.inspect's roofline says the
generic lowering loses (the TVM/Relay argument, PAPERS.md 1802.04799):

  * `flash_attention`     — blockwise online-softmax attention
  * `int8_matmul`         — int8 x int8 -> int32 serving matmul with the
                            per-channel rescale fused (QuantizedDense,
                            the mx.serve decode path)
  * `fused_update`        — one-VMEM-pass optimizer updates (Adam/AdamW
                            via FunctionalOptimizer; the fused-LAMB flat
                            master passes)
  * `moe_kernels`         — fused MoE dispatch/combine without the
                            (N, E, C) one-hot tensor (parallel/moe.py)
  * `paged_attention`     — one-token decode attention gathered through
                            an mx.pages block table (the paged serve
                            path), scalar-prefetch indexed so the dense
                            gathered operand never hits HBM
  * `paged_latent_attention` — the same walk over a LATENT cache (one
                            row a token with no head axis, key and value
                            of every head: multi-head latent attention in
                            absorbed form), a wave of pages brought once
                            and reduced by two matrix products
  * `kv_page_write`       — that step's write of the new keys and values
                            into the page arenas, in place and in the
                            layout `paged_attention` reads (and
                            `arena_head_dim`, the lane-width last
                            dimension the arenas rest row-major in)

Every kernel sits behind the `kernels=off|auto|on` knob with a bit-exact
XLA-native fallback (see `pallas_ops/_common.py`), ships an
interpret-mode CPU path (MXNET_TPU_PALLAS_INTERPRET=1 — tier-1
exercises the kernel code, not just the reference), and is benchmarked
pallas-vs-XLA by `benchmarks/bench_kernels.py`. `tools/lint_rules.py`
forbids `pl.pallas_call` outside this package.

Import hygiene: every submodule defers its `jax.experimental.pallas`
import to first kernel ENGAGEMENT (backend probe first), so importing
this package — which the QuantizedDense / FunctionalOptimizer / moe_ffn
hot paths do — never drags pallas into a kernels=off or CPU process
(ci/run.sh sanity asserts sys.modules stays clean after a trainer step).
"""
from . import _common
from . import fused_update
from . import moe_kernels
# the function re-exports shadow the same-named submodules on the
# package, as they always have; the module spelling stays
# importlib.import_module (see tests/unittest/test_flash_interpret.py)
from .flash_attention import flash_attention, mha_reference
from .int8_matmul import int8_matmul, int8_matmul_reference
from .kv_page_write import kv_page_write, kv_page_write_reference
from .paged_attention import paged_attention, paged_attention_reference
from .paged_latent_attention import (paged_latent_attention,
                                     paged_latent_attention_reference)

__all__ = ["flash_attention", "mha_reference", "int8_matmul",
           "int8_matmul_reference", "paged_attention",
           "paged_attention_reference", "paged_latent_attention",
           "paged_latent_attention_reference", "kv_page_write",
           "kv_page_write_reference", "fused_update", "moe_kernels",
           "_common"]
