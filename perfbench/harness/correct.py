"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests the run finished
(those sent in the lead-in included: above the knee few requests sent
in the window finish inside it) is drawn from the seed: the request with the most served tokens, the one
with the longest prompt, any the cell asks to be covered (priority
requests and requests riding a rebind, on four chips), and then others
in seeded order until some hundreds of tokens are in. For each, the
plain reference runs once over the prompt with the tokens the server
streamed, and the number compared is the widest gap by which a served
(greedy) token's logit lies below the reference's best logit at that
position. A served token that is the reference's argmax has gap 0; a
near-tie flipped by bf16 rounding has a gap of the size of that
rounding; a wrong token has a gap of the size of the logits' spread.

The prompts are the program's synthetic ones: the HTTP API takes a
prompt length, and the program draws a request's prompt ids from its
request id (``core/task_pool.prompt_token_ids``: numpy's default
generator seeded by ``abs(hash(req_id)) % 2**31``). :func:`prompt_ids`
repeats that rule here; ``run.py`` fixes ``PYTHONHASHSEED`` from the
run's seed so the prompts follow ``--seed``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from .traffic import rng_for


def prompt_ids(server_id: str, prompt_len: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(abs(hash(server_id)) % (1 << 31))
    return rng.integers(0, vocab, size=prompt_len)


def pick(records: Sequence, seed: int, must: Sequence = (),
         min_tokens: int = 384, max_requests: int = 12) -> List:
    """The sample: finished requests only."""
    done = [r for r in records if r.state == "done" and r.tokens
            and r.server_id]
    if not done:
        return []
    chosen: List = []

    def add(r):
        if r not in chosen and len(chosen) < max_requests:
            chosen.append(r)

    add(max(done, key=lambda r: (len(r.tokens), r.plan.prompt_len)))
    add(max(done, key=lambda r: (r.plan.prompt_len, len(r.tokens))))
    for r in must:
        if r in done:
            add(r)
    rng = rng_for(seed, 7919)
    for i in rng.permutation(len(done)):
        if sum(len(r.tokens) for r in chosen) >= min_tokens:
            break
        add(done[int(i)])
    return chosen


def _out_n(m: int) -> int:
    """Positions read per request: one shape for every answer up to 512
    tokens, so the reference compiles once per sequence length."""
    n = 512
    while n < m:
        n *= 2
    return n


def gaps(w: Dict, cfg: Dict, prompt: np.ndarray, served: Sequence[int],
         control: bool = False) -> Dict[str, float]:
    """Widest served-token gap of one request under the reference, and,
    with ``control``, the widest gap of the tokens the fp8 control puts
    first at the same positions."""
    from .reference import logits_at
    served = np.asarray(served, np.int64)
    m = len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    lo, n = len(prompt) - 1, _out_n(m)
    ref = np.asarray(logits_at(w, cfg, seq, lo, n))[:m]
    best = ref.max(axis=1)
    gap = best - ref[np.arange(m), served]
    out = {"gap": float(gap.max()), "tokens": m,
           "argmax_agree": int((gap == 0).sum())}
    if control:
        ctl = np.asarray(logits_at(w, cfg, seq, lo, n, quant="fp8"))[:m]
        pick_c = ctl.argmax(axis=1)
        out["control_gap"] = float((best - ref[np.arange(m), pick_c]).max())
    return out


def compare(w: Dict, cfg: Dict, sample: Sequence, vocab: int,
            control: bool = False) -> Dict[str, Optional[float]]:
    """The numbers compared over the whole sample."""
    worst, ctl, toks, agree = 0.0, 0.0, 0, 0
    for r in sample:
        g = gaps(w, cfg, prompt_ids(r.server_id, r.plan.prompt_len, vocab),
                 r.tokens, control)
        worst = max(worst, g["gap"])
        toks += g["tokens"]
        agree += g["argmax_agree"]
        if control:
            ctl = max(ctl, g["control_gap"])
    out = {"served_logit_gap": worst if sample else None,
           "tokens_compared": toks, "requests_compared": len(sample),
           "argmax_agree": agree}
    if control:
        out["control_logit_gap"] = ctl
    return out
