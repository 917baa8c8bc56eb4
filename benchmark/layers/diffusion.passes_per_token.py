"""Forward passes of the stage a delivered token costs under generation by
diffusion over blocks: the slot-passes of the traced stretch's decode launches
(denoise and commit passes, a pass counted once for every live slot that took
part in it) over the tokens its replies keep (positions revealed less those
behind a reply's asked length, which are dropped). From the attributes of the
program's ``serve.decode.done`` spans (its counters ``serve.diffusion.*``, read
in the fetch a launch makes anyway). ``(T + 1) / L`` where no block is cut and
no prompt ends inside one: 1.25 at ``L = T = 4``. None where the program
counts no such thing."""

from pb_core import load_by_path

_rows = load_by_path("layers/moe.rows_per_expert_read.py")


def read(facts):
    got = _rows.launch_counts(facts, "denoise_passes", "commit_passes",
                              "tokens", "cut_tokens")
    if not got or got[2] <= got[3]:
        return None
    denoise, commit, tokens, cut = got
    return (denoise + commit) / (tokens - cut)
