"""Share of the traced window in which no operation ran on the device."""


def read(facts):
    return 100.0 * facts['xplane']['idle_share']
