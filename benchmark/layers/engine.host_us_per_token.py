"""Host microseconds per output token: the growth over the window of the
engine's own ``serve.engine.host_sec`` (what a tick spends outside prefill
and decode launches) over the output tokens of the requests it finished."""


def read(facts):
    if not facts["out_tokens"]:
        return None
    return 1e6 * facts["host_sec"] / facts["out_tokens"]
