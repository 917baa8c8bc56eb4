"""Steps the host has handed over and the device has not finished, read at
each step program's start on the device: ``train.dispatch`` spans ended by
then minus step programs ended by then; the mean over the window's step
programs. The program starting counts once its dispatch has returned. The
depth is what a pause of the host can last before the device runs dry."""

import bisect

import pb_spans


def read(facts):
    cap = pb_spans.read(facts)
    if cap is None:
        return None
    dispatched = sorted(sp.end for sp in cap.spans.get("train.dispatch", ()))
    runs = cap.runs(pb_spans.TRAIN_STEP)
    if not dispatched or not runs:
        return None
    ended = sorted(end for _, _, end in runs)
    depth = [bisect.bisect_right(dispatched, start)
             - bisect.bisect_right(ended, start) for _, start, _ in runs]
    return sum(depth) / len(depth)
