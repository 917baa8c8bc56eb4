"""Peak bytes in use on the fullest chip after the window, in GiB."""


def read(facts):
    return facts["memory_peak_bytes"] / 2 ** 30
