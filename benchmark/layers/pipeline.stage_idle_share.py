"""Mean over the device planes of 1 - busy / window: what the pipeline's
bubble, its transport and the host leave idle on each stage's chip."""


def read(facts):
    tr = facts["trace"]
    shares = [1.0 - b / tr.window_s for b in tr.device_busy.values()]
    return 100.0 * sum(shares) / len(shares)
