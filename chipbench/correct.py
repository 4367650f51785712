"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed and always holding the
one with the most served tokens, is run through the plain float32
reference (:mod:`chipbench.reference`): each prompt followed by its served
tokens, in one forward pass.  At every position whose next token the
program served, the reference's best logit is compared with its logit for
the served token.  The widest such gap over the sample is the number
compared: greedy decoding of the configuration, done right in the stated
precision, serves tokens whose reference logit lies within rounding of the
best.

The limits are per cell, in ``chipbench/limits/<cell>.json``, each set
between the widest gap that sound runs of the program read and the
narrowest that the control reads; ``PERF.md`` gives the readings.  The
control is the reference computed with every matrix operand in float8, put
in the program's place: at the same positions of the same sequences, the
token it puts first is compared in the program's stead, through the same
limit, and has to come out not correct.  A mixture-of-experts cell also holds the program's
count of tokens its routing dropped to 0: the configuration routes
without drops.
"""

from __future__ import annotations

import json
import os

import numpy as np

from chipbench import model
from chipbench.reference import common as ref

REFERENCE_TOKENS = 32768       # reference input per run, in padded positions


def sample(run, seed, budget=REFERENCE_TOKENS):
    """The requests to compare: those that received tokens in the window,
    the one with the most served tokens first, then the rest in an order
    drawn from the seed while their reference input fits ``budget``."""
    cands = [tr for tr in run.tracks.values() if tr.req.generated
             and any(run.in_window(t) for t in tr.times)]
    if not cands:
        return []
    cands.sort(key=lambda tr: (-len(tr.req.generated), tr.ask.rid))
    cost = lambda tr: ref.bucket(len(tr.req.prompt)
                                 + len(tr.req.generated) - 1)
    chosen, used = [cands[0]], cost(cands[0])
    rest = cands[1:]
    for j in np.random.default_rng([seed, 2]).permutation(len(rest)):
        if used + cost(rest[j]) <= budget:
            chosen.append(rest[j])
            used += cost(rest[j])
    return chosen


def inputs(chosen):
    """Reference inputs of the sample: each prompt with its served tokens
    but the last, the rows whose next token was served, and those
    tokens."""
    seqs, rows, served = [], [], []
    for tr in chosen:
        p = np.asarray(tr.req.prompt, np.int32)
        g = np.asarray(tr.req.generated, np.int32)
        seqs.append(np.concatenate([p, g[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) + len(g) - 1))
        served.append(g)
    return seqs, rows, served


def limits_of(cell):
    path = os.path.join(cell.root, "chipbench", "limits", f"{cell.name}.json")
    with open(path) as f:
        return json.load(f)


def gaps(conf, seed, seqs, rows, served, control=False):
    """Widest gap of the served tokens under the reference and, with
    ``control``, widest gap of the tokens the float8 control would serve
    at the same positions."""
    key = model.param_key(seed)
    xs = ref.hidden(conf, key, seqs, "f32")
    token_sets = [served]
    if control:
        xq = ref.hidden(conf, key, seqs, "fp8")
        argq = [s[2] for s in ref.score(conf, key, xq, rows, [served],
                                        "fp8")]
        del xq
        token_sets.append(argq)
    scored = ref.score(conf, key, xs, rows, token_sets, "f32")
    widest = [max(float(np.max(best - at[j])) for best, at, _ in scored)
              for j in range(len(token_sets))]
    return widest


def check(run, cell, seed, dropped, control=False):
    """The numbers compared, each with its limit: ``{name: {"value",
    "limit"}}``; ``value`` None means nothing could be compared.  With
    ``control``, the control's tokens stand in the program's place; the
    program's own widest gap is kept in ``run.readings`` beside it."""
    limit = limits_of(cell)["logit_gap"]["limit"]
    chosen = sample(run, seed)
    checks = {}
    if chosen:
        seqs, rows, served = inputs(chosen)
        run.readings = gaps(cell.conf, seed, seqs, rows, served, control)
        checks["logit_gap"] = {"value": run.readings[-1], "limit": limit}
        run.checked = (len(chosen), int(sum(len(s) for s in served)))
    else:
        checks["logit_gap"] = {"value": None, "limit": limit}
        run.checked = (0, 0)
    if cell.conf["family"] == "moe":
        checks["tokens_dropped"] = {"value": dropped, "limit": 0}
    return checks


def passed(checks) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
