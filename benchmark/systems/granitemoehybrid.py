"""The program under test for the ``granitemoehybrid`` family: the paged
``DecodeEngine`` behind ``ServingHTTPServer``, built through the same entry
points as the other families' systems, with the benchmark's weights
(renamed, never copied) and the configuration's deployment settings as
constructor arguments. The model class is imported as this module is, so a
commit without the family fails before any weight is made."""
import gc
import os

from mxnet_tpu.serving.decode import GraniteHybridLM


def model_config(cfg):
    """The configuration file's keys under the model's own."""
    return dict(
        vocab=cfg['vocab_size'], max_len=cfg['max_position_embeddings'],
        hidden=cfg['hidden_size'], layer_types=cfg['layer_types'],
        eps=cfg['rms_norm_eps'], head_dim=cfg['head_dim'],
        heads=cfg['num_attention_heads'],
        kv_heads=cfg['num_key_value_heads'],
        mamba_heads=cfg['mamba_n_heads'],
        mamba_head_dim=cfg['mamba_d_head'],
        mamba_state=cfg['mamba_d_state'], mamba_conv=cfg['mamba_d_conv'],
        mamba_groups=cfg['mamba_n_groups'],
        mamba_chunk=cfg['mamba_chunk_size'],
        experts=cfg['published']['num_local_experts'],
        held_experts=cfg['held_experts'],
        top_k=cfg['num_experts_per_tok'],
        expert_hidden=cfg['intermediate_size'],
        shared_hidden=cfg['shared_intermediate_size'],
        embedding_multiplier=cfg['embedding_multiplier'],
        residual_multiplier=cfg['residual_multiplier'],
        attention_multiplier=cfg['attention_multiplier'],
        logits_scaling=cfg['logits_scaling'],
        dtype=cfg['precision']['weights'],
        **cfg['deployment'].get('model', {}))


def program_params(weights):
    """The reference's leaves under the program's parameter names: the
    same device buffers, no copy."""
    return {k.replace('.', '_'): v for k, v in weights.items()}


class Server:
    """``port`` answers ``POST /generate``; ``counts()`` and ``spans()``
    read the engine's counters and request spans."""

    def __init__(self, cfg, weights, traced):
        from mxnet_tpu import serving
        from mxnet_tpu.observability import trace
        dep = cfg['deployment']
        if traced:
            os.environ['MXNET_TPU_TRACE_BUFFER'] = '262144'
            trace.set_enabled(True)
        self._trace = trace
        prog = serving.freeze_decode(
            GraniteHybridLM(model_config(cfg)), program_params(weights),
            slots=dep['slots'], prefill_buckets=dep['prefill_buckets'],
            max_len=cfg['max_position_embeddings'],
            page_size=dep['page_size'], pages=dep['pages'],
            emit_logits=dep['emit_logits'])
        if not prog.paged:
            raise RuntimeError('freeze_decode did not choose the paged '
                               'program')
        self.sess = serving.InferenceSession(
            prog, warmup=True, max_queue=dep['max_queue'],
            max_new_tokens=dep['max_new_tokens'],
            timeout_s=dep['timeout_s'],
            prefill_interleave=dep['prefill_interleave'])
        self.srv = serving.ServingHTTPServer(self.sess, 0).start()
        self.port = self.srv.port
        self.slots = dep['slots']

    def counts(self):
        return dict(self.sess.status()['decode']['counts'])

    def spans(self):
        """The server's request spans (``GET /trace``, NDJSON)."""
        import json
        import urllib.request
        with urllib.request.urlopen('http://127.0.0.1:%d/trace'
                                    % self.port, timeout=60) as r:
            return [json.loads(ln) for ln in r.read().splitlines() if ln]

    def close(self):
        self.srv.stop()
        self.sess.close()
        self._trace.set_enabled(None)
        self.srv = self.sess = None
        # session, engine and watchdog refer to each other in cycles that
        # the collector takes two passes to free; the reference makes its
        # own 9.9 GB of weights next, so the program's must be gone
        gc.collect()
        gc.collect()
