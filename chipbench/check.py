"""Whether what the measured window served is correct.

Once the window has closed, the device memory peak has been read and
the program's state is freed, a sample of the requests it finished,
drawn from the seed and holding the longest of them, goes through the
plain reference (``reference/<name>.py``): one float32 forward pass
over each prompt with its served tokens.  For every served token the
number compared is how far its reference logit lies below the
reference's best at that position; the widest such gap over the sample
must stay within the cell's limit (``limits/<cell>.json``, set from
readings of sound runs and of the control, see ``calibrate.py``).

A request due in an open loop's window that never finishes, or one
that finishes with another number of tokens than it asked for, is not
correct either.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Verdict:
    correct: bool
    attempted: int
    failed: int
    compared: dict          # name -> {"value": x, "limit": y}
    lines: list             # the compared numbers, for standard error
    sample: list = dataclasses.field(default_factory=list)


def sample(logs: list, seed: int, tokens: int, most: int) -> list:
    """Finished requests to check: the longest (prompt plus output),
    then others in an order drawn from the seed, until ``tokens``
    served tokens or ``most`` requests."""
    if not logs:
        return []
    logs = sorted(logs, key=lambda g: g.req.rid)
    longest = max(logs, key=lambda g: (len(g.req.prompt) + len(g.tokens),
                                       -g.req.rid))
    rest = [g for g in logs if g is not longest]
    order = np.random.default_rng([int(seed), 5]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def sequence(log, length: int) -> tuple[np.ndarray, np.ndarray, slice]:
    """(prompt and served tokens but the last, padded to ``length``;
    the token each position should predict; the positions whose
    predictions were served)."""
    prompt = np.asarray(log.req.prompt, np.int32)
    served = np.asarray(log.tokens, np.int32)
    full = np.concatenate([prompt, served])
    if len(full) - 1 > length:
        raise ValueError(f"request {log.req.rid}: {len(full) - 1} tokens "
                         f"exceed the reference length {length}")
    seq = np.zeros((length,), np.int32)
    seq[:len(full) - 1] = full[:-1]
    target = np.zeros((length,), np.int32)
    target[:len(full) - 1] = full[1:]
    return seq, target, slice(len(prompt) - 1, len(full) - 1)


def _below_best(lg, target):
    import jax.numpy as jnp
    got = jnp.take_along_axis(lg, target[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got


def gaps(ref, dims, seed: int, log, length: int, *, control: bool = False
         ) -> np.ndarray:
    """Per served token: the reference's best logit minus the logit of
    the served token.  With ``control``, the token judged at each
    position is the one the fp8 control puts first instead."""
    import jax
    import jax.numpy as jnp
    seq, target, served = sequence(log, length)
    lg = ref.logits(dims, seed, seq)
    if control:
        target = jnp.argmax(ref.logits(dims, seed, seq, fp8=True), axis=-1)
    return np.asarray(jax.jit(_below_best)(lg, jnp.asarray(target)),
                      np.float64)[served]


def check(run, ref, dims, seed: int, *, control: bool = False) -> Verdict:
    """The verdict on what ``run`` served; with ``control``, the verdict
    on the fp8 control put in the program's place on the same sample
    (its tokens at each position, judged with the cell's own limits)."""
    lim = run.cell.limits
    logs = list(run.logs.values())
    finished = [g for g in logs if g.done]
    unfinished = len(logs) - len(finished) if run.cell.open_loop else 0
    miscounted = sum(len(g.tokens) != g.req.max_new_tokens for g in finished)
    chosen = sample(finished, seed, int(lim["sample_tokens"]),
                    int(lim.get("sample_requests", 64)))
    length = int(run.cell.traffic["engine"]["max_len"])
    widest = max((float(gaps(ref, dims, seed, g, length,
                             control=control).max())
                  for g in chosen), default=None)
    compared = {
        "logit_gap": {"value": widest, "limit": float(lim["logit_gap"])},
        "unfinished": {"value": unfinished, "limit": 0},
        "miscounted": {"value": miscounted, "limit": 0},
    }
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in compared.values())
    served = sum(len(g.tokens) for g in chosen)
    who = " (the fp8 control's tokens there)" if control else ""
    lines = [f"checked {len(chosen)} requests, {served} served tokens"
             f"{who}, against the float32 reference"]
    lines += [f"compared {k} {v['value']} limit {v['limit']}"
              for k, v in compared.items()]
    return Verdict(correct=ok, attempted=len(logs), failed=unfinished,
                   compared=compared, lines=lines, sample=chosen)
