"""Peak bytes in use on the fullest device over the chip's memory."""


def read(facts):
    return 100.0 * facts['memory_peak_bytes'] / facts['peaks']['hbm_bytes']
