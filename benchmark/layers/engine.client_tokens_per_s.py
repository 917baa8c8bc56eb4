"""The sum over the closed loop's clients of each client's output tokens per
second from its first reply inside the window to its last: the rate with no
request cut at the window's ends, so steadier from seed to seed than the
end-to-end rate it stands beside. Nothing where a client had fewer than two
replies inside the window."""


def read(facts):
    return facts["client_tokens_per_s"]
