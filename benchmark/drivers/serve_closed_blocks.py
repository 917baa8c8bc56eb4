"""Traffic kind ``serve_closed_blocks``: ``serve_closed``'s closed loop over a
model that generates by diffusion over blocks.

The loop, the warm-up, the lead-in, the window, the traced stretch, the rate
and the side file are ``drivers/serve_closed.py``'s own functions, loaded by
path as a module object of this driver's own; what is new is the comparison
that decides ``correct``, which that module is given in its own's place.

A token of such a reply is not the continuation of the tokens before it: it
was revealed at some denoise pass ``t`` of its block, from the logits of its
own position in the block's state before that pass (the positions revealed
earlier, the mask token elsewhere), over the clean earlier blocks.
``Response.reveal_pass`` carries ``t`` for every token, so the states can be
rebuilt from a finished request alone. For a sample of the window's
finished requests (the longest among them) the plain reference computes, in
ONE forward a request (``reference.denoise_hidden``: the clean sequence and
its ``T`` noisy copies), the logits of every position that was masked
before each pass, and two numbers are read:

* ``served_logit_gap``: over every token, at the pass that revealed it, how
  far the served token's logit lies below the reference's best there;
* ``reveal_confidence_gap``: over every pass of every block, how far the
  log-confidence (log max softmax) of the least sure position the program
  revealed lies below that of the surest position it left masked, both by
  the reference (0 where the program revealed what the reference would).

Both are the widest value of a run: what rounding alone can reach grows with
the tokens compared, and a fault that moves every logit a little (the top-8
weights not renormalised) hides under it. So their MEANS are compared too
(``served_logit_gap_mean`` over the tokens, ``reveal_confidence_gap_mean``
over the passes): rounding moves few tokens and averages out, a fault in the
mathematics moves them all. The cell's limits file names the numbers
compared.

A reply's last block, where the reply ends inside it, is not compared: the
positions behind the reply's end took part in its states and were dropped
with it. ``ok_requests_of_wrong_length`` holds every reply to its length.
"""

from __future__ import annotations

import copy
import gc
import importlib.util
import json
import os
import time

import jax.numpy as jnp
import numpy as np

import pb_core


def _own_copy_of(rel: str):
    """``benchmark/<rel>`` as a module object of this driver's own (not the
    one ``pb_core.load_by_path`` shares): giving it another comparison
    leaves the cells that run it as it is alone."""
    path = os.path.join(pb_core.HERE, rel)
    spec = importlib.util.spec_from_file_location(
        "pb_drivers__serve_closed__for_blocks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _own_copy_of("drivers/serve_closed.py")
serve_window = base.serve_window
sample_finished = base.sample_finished


def _whole_blocks(prompt, resp, L):
    """``(sequence, reveal passes)`` of a reply up to its last whole
    block, or None where no whole block was generated."""
    n = len(prompt)
    m = (n + len(resp.tokens)) // L * L - n
    if m <= 0 or resp.reveal_pass is None:
        return None
    return list(prompt) + list(resp.tokens[:m]), list(resp.reveal_pass[:m])


def _rows_of(rev, first_gen, T):
    """The rows of the ``[T, n]`` noisy copies that hold a position masked
    before its copy's pass: ``(pass, column, index into rev)`` of each, in
    pass-major order; column ``first_gen + j`` is generated token ``j``."""
    rev = np.asarray(rev)
    t, j = np.nonzero(rev[None, :] >= np.arange(T)[:, None])
    return t, first_gen + j, j


def _confidence_gaps(lc, shown, key):
    """``lc [rows]``: the reference's log-confidence at each masked row;
    ``shown [rows]``: whether the side under test revealed that row's
    position at that row's pass; ``key [rows]``: the row's (block, pass).
    For every (block, pass) that both revealed and left a position masked:
    the surest row left masked less the least sure row shown, at least 0."""
    gaps = []
    for k in np.unique(key):
        rows = key == k
        kept, out = lc[rows & ~shown], lc[rows & shown]
        if len(kept) and len(out):
            gaps.append(max(0.0, float(kept.max() - out.min())))
    return gaps


def _most_confident(lc, key, n_shown):
    """Which masked rows a side that ranks by ``lc`` reveals: in every
    (block, pass) ``key`` its ``n_shown[key]`` surest, a tie to the earlier
    position."""
    shown = np.zeros(len(lc), bool)
    for k in np.unique(key):
        rows = np.nonzero(key == k)[0]
        order = rows[np.argsort(-lc[rows], kind="stable")]
        shown[order[:n_shown[k]]] = True
    return shown


def replay_gaps(cell, seed, sample, sent, precision="f32"):
    """The two gaps over the sample (module docstring); with ``precision``
    below float32 also the control's: the tokens and the reveals that
    precision puts first in the served states, judged by the float32
    reference as the served ones are."""
    cfg, tr = cell.cfg, cell.traffic
    ref = cell.family.reference
    L, T, _ = ref.generation(cfg)
    chunk = tr["check_rows"]
    t_start = time.perf_counter()
    weights = ref.make_weights(cfg, seed)
    out = {"served_tokens": 0, "requests": 0, "rows": 0}
    # every token's logit gap and every pass's confidence gap, by side
    gaps = {"served_logit": [], "reveal_confidence": [],
            "control_logit": [], "control_confidence": []}

    def logits_of(hid, t, col, chosen, prec):
        """Per masked row: best logit, the chosen token's, the token put
        first, the log-confidence; the head ``chunk`` rows at a time."""
        rows = hid[t, col]                                  # [rows, d]
        parts = []
        for lo in range(0, len(t), chunk):
            part = rows[lo:lo + chunk]
            pad = chunk - part.shape[0]
            got = ref.best_and_chosen(
                ref.head(weights, jnp.pad(part, ((0, pad), (0, 0))), cfg,
                         prec),
                jnp.pad(jnp.asarray(chosen[lo:lo + chunk]), (0, pad)))
            parts.append([np.asarray(a)[:part.shape[0]] for a in got])
        return [np.concatenate(x) for x in zip(*parts)]

    for resp in sample:
        prompt = sent[resp.request_id][0]
        whole = _whole_blocks(prompt, resp, L)
        if whole is None:
            continue
        seq, rev = whole
        n = len(prompt)
        first = n // L * L
        noisy = ref.noisy_states(seq, n, rev, first, cfg)
        clean = ref.kept_tokens(seq, n, rev, cfg)
        t, col, j = _rows_of(rev, n - first, T)
        at_reveal = np.asarray(rev)[j] == t
        chosen = np.asarray(seq[n:], np.int32)[j]
        # a row's (block, pass): T is far under 64
        key = ((n + j) // L).astype(np.int64) * 64 + t
        hid = ref.denoise_hidden(weights, clean, noisy, first, cfg,
                                 pad_to=tr["check_pad_to"])
        best, got, _, lc = logits_of(hid, t, col, chosen, "f32")
        gaps["served_logit"].append((best - got)[at_reveal])
        gaps["reveal_confidence"].append(
            _confidence_gaps(lc, at_reveal, key))
        if precision != "f32":
            low = ref.denoise_hidden(weights, clean, noisy, first, cfg,
                                     precision=precision,
                                     pad_to=tr["check_pad_to"])
            _, _, put_first, lc_low = logits_of(low, t, col, chosen,
                                                precision)
            del low
            # the control's tokens, at the rows the served ones were
            # revealed at, by the float32 reference
            _, got_low, _, _ = logits_of(hid, t, col, put_first, "f32")
            gaps["control_logit"].append((best - got_low)[at_reveal])
            n_shown = {k: int(at_reveal[key == k].sum())
                       for k in np.unique(key)}
            gaps["control_confidence"].append(_confidence_gaps(
                lc, _most_confident(lc_low, key, n_shown), key))
        del hid
        out["served_tokens"] += len(rev)
        out["rows"] += len(t)
        out["requests"] += 1
    del weights
    for name, parts in gaps.items():
        all_ = np.concatenate([np.asarray(p, np.float64) for p in parts]) \
            if parts else np.zeros(0)
        out[name + "_gap"] = float(all_.max()) if all_.size else 0.0
        out[name + "_gap_mean"] = float(all_.mean()) if all_.size else 0.0
    out["seconds"] = round(time.perf_counter() - t_start, 2)
    return out


# a side's numbers, as the limits file names them
NUMBERS = ("served_logit_gap", "reveal_confidence_gap",
           "served_logit_gap_mean", "reveal_confidence_gap_mean")


def _side(got, logit="served_logit", confidence="reveal_confidence"):
    """``got``'s numbers of one side under the names the limits have."""
    return {"served_logit_gap": got[logit + "_gap"],
            "reveal_confidence_gap": got[confidence + "_gap"],
            "served_logit_gap_mean": got[logit + "_gap_mean"],
            "reveal_confidence_gap_mean": got[confidence + "_gap_mean"]}


def reference_gaps(cell, seed, sample, sent, precision="f32"):
    """What ``serve_closed.run`` asks for under this name: its
    ``served_logit_gap`` entry is handed to :func:`compare` as it is, so it
    holds both numbers compared."""
    got = replay_gaps(cell, seed, sample, sent, precision)
    return dict(got, served_logit_gap=_side(got))


def compare(checks, done, sent, gaps, limits):
    """Every ``ok`` request has the length it asked for, and each of
    :data:`NUMBERS` that the limits name (``gaps``: None where nothing was
    compared) is within its limit."""
    wrong = sum(1 for _, r in done if r.status == "ok"
                and len(r.tokens) != sent[r.request_id][1])
    checks.add("ok_requests_of_wrong_length", wrong,
               limits["ok_requests_of_wrong_length"])
    for name in NUMBERS:
        if name in limits:
            checks.add(name, None if gaps is None else gaps[name],
                       limits[name])


# ``base.run`` finds the comparison by these two names among its module's
# globals; should it stop, this cell's ``correct`` would be decided by the
# continuation check of ``serve_closed.py`` and nobody would be told
if not {"reference_gaps", "compare"} <= set(base.run.__code__.co_names):
    raise ImportError("drivers/serve_closed.py run() no longer calls "
                      "reference_gaps and compare by name: "
                      "serve_closed_blocks.py cannot give it its comparison")
base.reference_gaps = reference_gaps
base.compare = compare
run = base.run


def with_fault(cell, fault):
    """The cell with ``fault`` planted in its configuration (a shallow
    copy: the reference reads ``cfg["fault"]``)."""
    other = copy.copy(cell)
    other.cfg = dict(cell.cfg, fault=fault)
    return other


def readings(cell, seeds, devices, control=True, faults=True, seconds=8.0):
    """For ``tools/readings.py``: for each seed a short window at the
    cell's own load, then the served tokens' two gaps against the float32
    reference, the float8 control's, and the served tokens' against the
    reference with each of its ``FAULTS`` planted, every side judged by the
    cell's committed limits: ``correct`` has to read true for the program
    and false for every other side."""
    del devices
    planted = cell.family.reference.FAULTS if faults else ()
    out = []
    for seed in seeds:
        w = serve_window(cell, seed, seconds)
        done, sent = w["done"], w["loop"].sent
        del w
        gc.collect()
        sample = sample_finished(done, sent, seed,
                                 cell.traffic["check_requests"])
        got = replay_gaps(cell, seed, sample, sent,
                          precision="fp8" if control else "f32")
        row = dict(got, seed=seed, finished=len(done))
        sides = {"program": _side(got)}
        if control:
            sides["control_fp8"] = _side(got, "control_logit",
                                         "control_confidence")
        for fault in planted:
            bad = replay_gaps(with_fault(cell, fault), seed, sample, sent)
            sides["fault_" + fault] = _side(bad)
            row[f"fault_{fault}_gaps"] = sides["fault_" + fault]
        for side, gaps in sides.items():
            checks = pb_core.Checks()
            compare(checks, done, sent, gaps if row["requests"] else None,
                    cell.limits)
            row[side + "_correct"] = checks.correct
        out.append(row)
        print(json.dumps(row), flush=True)
    return out
