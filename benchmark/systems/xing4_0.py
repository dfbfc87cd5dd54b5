"""The program under test for the ``xing4_0`` family: the paged
``DecodeEngine`` behind ``ServingHTTPServer``, built through the same entry
points as the other families' systems, with the benchmark's weights
(renamed, never copied) and the configuration's deployment settings as
constructor arguments. The model class is imported as this module is, so a
commit without the family fails before any weight is made."""
import gc
import os

from mxnet_tpu.serving.decode.xing4 import Xing4LM


def model_config(cfg):
    """The configuration file's keys under the model's own."""
    rs = cfg['rope_scaling']
    return dict(
        vocab=cfg['vocab_size'], max_len=cfg['max_position_embeddings'],
        hidden=cfg['hidden_size'], layers=cfg['num_hidden_layers'],
        dense_layers=cfg['first_k_dense_replace'], eps=cfg['rms_norm_eps'],
        heads=cfg['num_attention_heads'], q_rank=cfg['q_lora_rank'],
        kv_rank=cfg['kv_lora_rank'], nope_dim=cfg['qk_nope_head_dim'],
        rope_dim=cfg['qk_rope_head_dim'], v_dim=cfg['v_head_dim'],
        dense_hidden=cfg['intermediate_size'],
        experts=cfg['published']['n_routed_experts'],
        held_experts=cfg['held_experts'],
        top_k=cfg['num_experts_per_tok'],
        expert_hidden=cfg['moe_intermediate_size'],
        shared_hidden=cfg['n_shared_experts'] * cfg['moe_intermediate_size'],
        routed_scale=cfg['routed_scaling_factor'], hc_mult=cfg['hc_mult'],
        hc_iters=cfg['hc_sinkhorn_iters'], hc_eps=cfg['hc_eps'],
        hc_clamp=(cfg['mhc_h_res_clamp_min'], cfg['mhc_h_res_clamp_max']),
        rope_theta=cfg['rope_theta'],
        yarn=dict(factor=rs['factor'],
                  original_max=rs['original_max_position_embeddings'],
                  beta_fast=rs['beta_fast'], beta_slow=rs['beta_slow'],
                  mscale=rs['mscale'], mscale_all_dim=rs['mscale_all_dim']),
        mtp=bool(cfg['num_nextn_predict_layers']),
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
            Xing4LM(model_config(cfg)), program_params(weights),
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
        # own 5.9 GB of weights next, so the program's must be gone
        gc.collect()
        gc.collect()
