"""95th percentile of ``Response.ttft`` over all requests the window
finished (a failed one counting as the window's length). The window is the
whole untraced one, also in the traced run: some 150 requests, not the
dozen of the traced stretch."""


def read(facts):
    return facts["ttft_p95_ms"]
