"""Tokens that decode steps emitted per step over the slots the engine has:
the engine's own ``decode.counts`` over the window (a prefill emits its
sequence's first token outside any step, so those are taken off)."""


def read(facts):
    if not facts.get('steps'):
        return None
    stepped = facts['engine_tokens'] - facts['engine_prefills']
    return 100.0 * stepped / (facts['steps'] * facts['slots'])
