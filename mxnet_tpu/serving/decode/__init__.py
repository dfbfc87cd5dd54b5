"""Autoregressive decode engine (docs/SERVING.md "Autoregressive
decoding").

Generation through the serving engine, compiler-first: a gluon RNN /
transformer language model freezes into TWO ahead-of-time program
kinds — a bucketed **prefill** that lands a prompt's state/KV prefix
in a preallocated slot cache, and ONE fixed-shape **decode step**
that advances every in-flight sequence a token with O(1)
``lax.dynamic_update_slice`` cache updates on donated buffers — and a
**continuous batcher** schedules sequences in and out of the slot
register file at token granularity::

    prog   = decode.freeze_decode((embedding, lstm, dense))
    prog.save('model.frozen')          # mxnet_tpu.frozen.v1 (decode)
    sess   = serving.InferenceSession(prog)
    stream = sess.generate(prompt_ids, max_new_tokens=64, eos_id=2)
    for tok in stream: ...             # per-token streaming

Module map: ``cache`` (slot-addressed preallocated caches + O(1)
update helpers), ``model`` (RNN-LM and causal-transformer families —
one math path shared by prefill, step, and the uncached reference so
cached decode is bit-identical to the whole-sequence forward),
``cohere2`` (the ``cohere2_moe`` family: parallel attention + expert
block, sliding-window and full layers in one paged cache manager),
``granite`` (the ``granitemoehybrid`` family: Mamba-2 layers whose
recurrent state sits in slot entries beside an attention layer's
pages), ``blocks`` (the held-expert layer and the attention both share),
``program`` (AOT compile + frozen.v1 persistence + CPU fallback),
``engine`` (continuous batching, admission control, breaker/watchdog
at site ``serving.decode``).
"""
from __future__ import annotations

from .cache import CacheSpec, cache_bytes, init_cache, write_position, \
    write_slot
from .cohere2 import Cohere2MoELM, init_cohere2_moe_lm
from .engine import DecodeEngine, DrainTimeout, GenerateStream
from .granite import GraniteHybridLM, init_granite_hybrid_lm
from .model import (DecodeModel, FamilyUnsupported, RNNLM, TransformerLM,
                    from_gluon_rnn_lm, init_rnn_lm, init_transformer_lm,
                    model_from_config)
from .paged import (PageAllocator, PagedCacheSpec, PageOwner,
                    PrefixCache, pool_bytes)
from .program import (DecodeProgram, PagedDecodeProgram, freeze_decode,
                      load_decode)
from .seqstate import SEQSTATE_SCHEMA, SeqStateError

__all__ = [
    'CacheSpec', 'cache_bytes', 'init_cache', 'write_position',
    'write_slot', 'DecodeEngine', 'DrainTimeout', 'GenerateStream',
    'DecodeModel', 'RNNLM', 'TransformerLM', 'Cohere2MoELM',
    'init_cohere2_moe_lm', 'GraniteHybridLM', 'init_granite_hybrid_lm',
    'FamilyUnsupported', 'from_gluon_rnn_lm',
    'init_rnn_lm', 'init_transformer_lm', 'model_from_config',
    'DecodeProgram', 'PagedDecodeProgram', 'PageAllocator',
    'PagedCacheSpec', 'PageOwner', 'PrefixCache', 'pool_bytes',
    'freeze_decode',
    'load_decode', 'SEQSTATE_SCHEMA', 'SeqStateError',
]
