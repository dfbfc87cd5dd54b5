"""Share of the device's idle time that ``xplane.attribute`` put under the
host span ``name`` (``no span``: no host span at all was open over the gap),
over the ten longest rows of ``idle_gaps`` that reach ``facts``. 0 where
there are gaps and none bears the name."""


def read(facts, name):
    rows = (facts.get('xplane') or {}).get('idle_gaps') or []
    whole = sum(t for _, t in rows)
    if not whole:
        return None
    return 100.0 * sum(t for n, t in rows if n == name) / whole
