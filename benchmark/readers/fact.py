"""A plain count the entry took itself, by its key."""


def read(facts, key):
    return facts.get(key)
