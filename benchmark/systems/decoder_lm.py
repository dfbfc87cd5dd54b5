"""The program under test for the ``decoder_lm`` family: the paged
``DecodeEngine`` behind ``ServingHTTPServer``, built as ``chip_smoke.py``
builds it, with the benchmark's weights and the configuration's deployment
settings as constructor arguments."""
import os


def program_params(weights):
    """The reference's leaves under the program's parameter names."""
    import jax.numpy as jnp
    out = {k.replace('.', '_'): v for k, v in weights.items()}
    out['out_bias'] = jnp.zeros((weights['embed'].shape[0],), 'float32')
    return out


class Server:
    """``port`` answers ``POST /generate``; ``counts()`` and ``spans()``
    read the engine's counters and request spans."""

    def __init__(self, cfg, weights, traced):
        from mxnet_tpu import serving
        from mxnet_tpu.observability import trace
        from mxnet_tpu.serving import decode
        dep = cfg['deployment']
        if traced:
            os.environ['MXNET_TPU_TRACE_BUFFER'] = '262144'
            trace.set_enabled(True)
        self._trace = trace
        model = decode.TransformerLM(dict(
            vocab=cfg['vocab_size'], units=cfg['n_embd'],
            hidden=cfg['intermediate_size'], layers=cfg['n_layer'],
            heads=cfg['n_head'], max_len=cfg['n_positions'],
            eps=cfg['layer_norm_epsilon']))
        prog = serving.freeze_decode(
            model, program_params(weights), slots=dep['slots'],
            prefill_buckets=dep['prefill_buckets'],
            max_len=cfg['n_positions'], page_size=dep['page_size'],
            pages=dep['pages'], emit_logits=dep['emit_logits'])
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
