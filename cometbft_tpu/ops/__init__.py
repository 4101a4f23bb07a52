"""TPU compute plane — JAX/XLA kernels.

The only data-parallel compute in a BFT node is signature verification
(SURVEY.md §2.10); these modules implement it as batched integer-limb
arithmetic that XLA fuses into large elementwise launches:

  field.py          — GF(2^255-19) limb arithmetic
  curve.py          — edwards25519 group ops + scalar multiplication
  sha512.py         — in-device SHA-512 (vote sign-bytes hashing)
  scalar.py         — arithmetic mod the group order L
  ed25519_verify.py — the batch-verify kernel + BatchVerifier provider

64-bit integer mode is required (limb products accumulate in i64), so
importing this package enables jax x64 process-wide before any tracing.
This is a deliberate global: the framework is standalone node software
that owns its process. Embedders who must keep 32-bit defaults should
isolate verification in a worker process (the node runtime never mixes
these kernels with float ML workloads in-process).
"""

import os

import jax

from cometbft_tpu.utils.env import flag_from_env
from cometbft_tpu.utils.trace import TRACER

jax.config.update("jax_enable_x64", True)

# One clock: every TRACER span open during a jax.profiler session also
# stands in the trace's host plane under its own name, beside the
# device programs (outside a session the annotation is a no-op in the
# runtime).
TRACER.set_annotator(jax.profiler.TraceAnnotation)

# Persistent XLA compilation cache: a cold verify-kernel compile is
# half a minute to a minute per program; a warm cache makes every later
# process start in seconds.  ONE place decides the directory: when
# JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and nothing is
# set here; otherwise the cache lives at the FIXED path
# <checkout>/.xla_cache (git-ignored) — the path is part of the cache
# key, so it must never move between runs.  Opt out with
# CMT_TPU_NO_COMPILE_CACHE=1.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".xla_cache",
)


if not flag_from_env("CMT_TPU_NO_COMPILE_CACHE"):
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
