"""Model operations per token, from a configuration's sizes.

Counts the multiply-adds the model requires (two operations each), not
what a program happens to compute: no padding, no recomputation, the
LM head once per generated token.
"""

from __future__ import annotations


def layer_matmul_params(dims) -> int:
    """Weights one layer multiplies per token: Q, K, V, O and the three
    SwiGLU matrices."""
    d, hd = dims.d, dims.head_dim
    attn = d * dims.heads * hd + 2 * d * dims.kv_heads * hd \
        + dims.heads * hd * d
    return attn + 3 * d * dims.ff


def attention_flops(dims, context: int) -> int:
    """Score and value products of one token against ``context`` keys,
    over every layer: ``2 * 2 * context * head_dim`` per query head."""
    return 4 * context * dims.heads * dims.head_dim * dims.layers


def token_flops(dims, position: int, head: bool) -> int:
    """Operations for the token at ``position`` (0-based; it attends to
    ``position + 1`` keys), with the LM head if it yields logits."""
    f = 2 * dims.layers * layer_matmul_params(dims) \
        + attention_flops(dims, position + 1)
    if head:
        f += 2 * dims.d * dims.vocab
    return f


def prefill_flops(dims, start: int, end: int, head: bool) -> int:
    """Operations for prompt positions ``start .. end - 1``; ``head``
    adds the LM head for the last of them (the prompt's first token)."""
    n = end - start
    f = n * 2 * dims.layers * layer_matmul_params(dims)
    # sum of (p + 1) over p in [start, end)
    keys = (end * (end + 1) - start * (start + 1)) // 2
    f += 4 * keys * dims.heads * dims.head_dim * dims.layers
    if head:
        f += 2 * dims.d * dims.vocab
    return f


def served_flops(run, t_from: float, t_to: float) -> int | None:
    """Model operations of the tokens the program computed in
    ``[t_from, t_to]`` (host clock): prompt tokens from its own
    ``serve.prefill_chunk`` (``offset``..``end``) and ``serve.admit``
    (``prompt_len``) spans, generated tokens as the client received
    them.  None without the program's spans."""
    if run.spans is None:
        return None
    dims = run.dims
    total = 0
    for s in run.spans:
        if s.get("type") != "span" or not t_from <= s["t"] <= t_to:
            continue
        log = run.logs.get(s.get("rid"))
        if log is None:
            continue
        n = len(log.req.prompt)
        if s["name"] == "serve.prefill_chunk":
            total += prefill_flops(dims, s["offset"], s["end"],
                                   head=s["end"] == n)
        elif s["name"] == "serve.admit":
            total += prefill_flops(dims, 0, s["prompt_len"], head=True)
    for log in run.logs.values():
        n = len(log.req.prompt)
        for j, t in enumerate(log.times[1:], start=1):
            if t_from <= t <= t_to:
                total += token_flops(dims, n + j - 1, head=True)
    return total
