"""Device-idle milliseconds per decode step under the host spans whose name
starts with ``prefix``. ``xplane.attribute`` gives a gap to the innermost
span over its middle, so under the scheduler's ``eng.tick`` spans this is
the idle time in which the scheduler thread ran its own Python, inside no
call of the runtime (those keep their own rows: ``DevicePut``, ...). Only
the ten longest rows of ``idle_gaps`` reach ``facts``."""


def read(facts, prefix):
    rows = (facts.get('xplane') or {}).get('idle_gaps') or []
    hits = [t for name, t in rows if name.startswith(prefix)]
    if not hits or not facts.get('steps'):
        return None
    return 1e3 * sum(hits) / facts['steps']
