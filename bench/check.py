"""Whether what the window served is correct: a comparison with the plain
float32 reference.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the
reference: the prompt window exactly as the engine served it (the last
``prefill_len`` prompt tokens, left-padded with id 0) followed by the
served tokens. At each served position the reference's logits say how far
the served token's logit lies below the reference's best. Greedy decoding
at exact arithmetic gives 0 everywhere; rounding in the served precision
picks the second candidate where two nearly tie, with a gap below the
rounding's size. The cell's file names the numbers compared and their
limits (``check.limits``): the gaps' sum over the number of positions at
which the reference's best two candidates lie within :data:`NEAR_TIE` of
each other (``near_tie_gap``). Only near ties can flip, and how many a run
meets depends on the seed's weights; per near tie, the sum grows as the
square of the rounding error, so it tells the served bfloat16 from int8
where the widest gap (``logit_gap``) and the plain mean
(``mean_logit_gap``), both reported beside it, do not.

The control (``control=True``) reads the same gap for the token that the
reference computed in the precision below the served one (the reference
module's ``CONTROL``: int8 operands for bfloat16) puts first at each of
those positions: the lower precision
the comparison has to reject.
:func:`control_readings` puts those readings in the program's place, so
the same :func:`verdict` judges them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Window


@dataclasses.dataclass
class Sample:
    rids: List[int]
    sequences: List[np.ndarray]    # window + served tokens but the last
    positions: List[np.ndarray]    # positions whose logits pick a token
    served: List[np.ndarray]       # the served tokens


def served_window(prompt: np.ndarray, prefill_len: int) -> np.ndarray:
    """The prefill window the engine serves: the last ``prefill_len``
    tokens of the prompt, left-padded with id 0."""
    p = np.asarray(prompt, np.int32)[-prefill_len:]
    return np.pad(p, (prefill_len - len(p), 0))


def draw(win: Window, prefill_len: int, seed: int, n_requests: int
         ) -> Sample:
    """Up to ``n_requests`` requests that finished in the window: the one
    with the most served tokens, then others in an order drawn from the
    seed."""
    done = [t for t in win.tracks
            if t.done_at is not None and t.done_at <= win.close
            and win.statuses.get(t.gen.rid) == "ok" and t.req.output]
    if not done:
        return Sample([], [], [], [])
    done.sort(key=lambda t: (-len(t.req.output), t.gen.rid))
    rest = done[1:]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    picked = [done[0]] + [rest[i] for i in order[:n_requests - 1]]
    s = Sample([], [], [], [])
    for t in picked:
        out = np.asarray(t.req.output, np.int32)
        s.rids.append(t.gen.rid)
        s.sequences.append(np.concatenate(
            [served_window(t.gen.prompt, prefill_len), out[:-1]]))
        s.positions.append(prefill_len - 1 + np.arange(len(out)))
        s.served.append(out)
    return s


# Reference margins below this are near ties (logits of random weights
# have unit spread).
NEAR_TIE = 0.05


@jax.jit
def _gaps(lg, picks):
    """``lg`` (P, V) reference logits, ``picks`` (k, P) token ids -> (k + 1,
    P): each pick's gap below the best, then the best's margin over the
    second."""
    top = jax.lax.top_k(lg, 2)[0]
    chosen = jnp.take_along_axis(lg, picks.T, axis=-1).T
    return jnp.concatenate([top[None, :, 0] - chosen,
                            (top[:, 0] - top[:, 1])[None]])


_argmax = jax.jit(lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))


def widest_gap(ref, shape, seed: int, sample: Sample, dims: Tuple[int, int,
               int], control: bool = False) -> dict:
    """Gaps of the served tokens (and, with ``control``, of the lower-
    precision reference's first choices) below the reference's best, and
    the reference's own margins. ``dims`` (longest sequence, requests,
    tokens per request) fix the reference's shapes."""
    readings = {"tokens_checked": int(sum(len(x) for x in sample.served)),
                "requests_checked": len(sample.rids)}
    if not sample.rids:
        return dict(readings, logit_gap=None)
    length, rows, n_pos = dims

    def run(quant, reduce):
        return ref.sequence_logits(shape, seed, sample.sequences,
                                   sample.positions, quant=quant,
                                   reduce=reduce, length=length, rows=rows,
                                   n_pos=n_pos)

    def padded(tokens):
        return [np.pad(np.asarray(x, np.int32), (0, n_pos - len(x)))
                for x in tokens]

    t0 = time.perf_counter()
    picks = [padded(sample.served)]
    if control:
        picks.append(padded(run(ref.CONTROL[shape.served],
                                lambda lg, i: _argmax(lg))))
        readings["control_s"] = time.perf_counter() - t0
    t1 = time.perf_counter()
    got = run(None, lambda lg, i: _gaps(
        lg, jnp.asarray(np.stack([p[i] for p in picks]))))
    readings["reference_s"] = time.perf_counter() - t1
    # Per request, in the order drawn (the longest first).
    readings["margins"] = [g[-1].tolist() for g in got]
    ties = max(1, int(sum((g[-1] < NEAR_TIE).sum() for g in got)))
    readings["near_ties"] = ties
    for k, name in enumerate(["", "control_"][:len(picks)]):
        g = np.concatenate([x[k] for x in got])
        readings[name + "gaps"] = [x[k].tolist() for x in got]
        readings[name + "near_tie_gap"] = float(g.sum() / ties)
        readings[name + "logit_gap"] = float(g.max())
        readings[name + "mean_logit_gap"] = float(g.mean())
    return readings


def control_readings(readings: dict) -> dict:
    """The control's readings in the program's place, for :func:`verdict`:
    its gaps stand where the served tokens' would."""
    return dict(readings, **{k: readings.get("control_" + k) for k in (
        "near_tie_gap", "logit_gap", "mean_logit_gap")})


def verdict(readings: dict, failed: int, limits: dict
            ) -> Tuple[bool, dict]:
    """``correct`` and each number compared beside its limit: every
    reading that ``limits`` names is held under its limit, the tokens
    checked at or above ``min_tokens_checked``."""
    checks = {name: {"value": readings.get(name), "limit": lim}
              for name, lim in limits.items()
              if name != "min_tokens_checked"}
    checks.update({
        "failed_requests": {"value": failed, "limit": 0},
        "tokens_checked": {"value": readings.get("tokens_checked", 0),
                           "limit": limits["min_tokens_checked"],
                           "at_least": True},
    })
    ok = True
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        if v is None or lim is None:
            ok = False
        elif c.get("at_least"):
            ok &= v >= lim
        else:
            ok &= v <= lim
    return bool(ok), checks
