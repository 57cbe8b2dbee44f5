"""Whether what the timed path served is correct.

Once the window has closed and the engine is gone, two samples of the
requests that finished by the close, each drawn from the seed and always
with the longest request of its kind, are run through the plain float32
reference of the configuration's architecture (bench/reference/<key>.py,
passed in as `ref`), each prompt with its served tokens:

- greedy requests: at every served position the reference's best logit
  minus the logit of the token served is the logit gap: 0 where the
  program picked the reference's argmax, small at a near-tie that bf16
  rounding flipped, large where the token is wrong;
- sampled requests (temperature, top-k, top-p): the reference's own
  top-k/top-p filter at that position keeps every logit at or above a
  threshold; the mask gap is how far the served token's logit lies below
  it: 0 for a token the filter keeps, small for one that bf16 rounding
  moved across the edge, large for one a broken mask let through.

The cell's file (bench/cells/<cell>.json) names under `limits` the
statistics compared (the mean logit gap over all greedy tokens, the mean
mask gap over all sampled tokens) with their limits, set from the cell's
own readings; each kind of request a limit names must also reach the
configuration's least number of compared tokens. Every finished request
must hold exactly its requested number of in-vocabulary tokens.

The control (bench/control.py) reads the same gaps for the tokens the
int8 reference (`logits_at(..., quant=True)`) puts in the program's place:
its argmax for greedy rows, and for sampled rows a token drawn
(Gumbel-max, seeded) from the int8 logits' own filtered set.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

KINDS = ("greedy", "sampled")
# the statistics of each kind's gaps (statistics() names them so)
GAP = {"greedy": "logit", "sampled": "mask"}


def pick(recs, w1: float, seed: int, target_tokens: int,
         max_requests: int, kind: str) -> list:
    """Requests of one kind finished by w1: the longest, then others in a
    seeded order until target_tokens served tokens or max_requests."""
    done = [r for r in recs if (r.spec.sampling is None) == (kind == "greedy")
            and r.t_done is not None and r.t_done <= w1]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.plen + len(r.req.tokens)), r.spec.rid))
    rest = done[1:]
    order = np.random.default_rng(
        np.random.SeedSequence([seed, KINDS.index(kind) + 1]))\
        .permutation(len(rest))
    out, n = [done[0]], len(done[0].req.tokens)
    for i in order:
        if n >= target_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].req.tokens)
    return out


def wrong_length(recs, w1: float, vocab: int) -> int:
    """Requests finished by w1 whose output is not exactly their gen_len
    tokens, all in the vocabulary."""
    return sum(1 for r in recs if r.t_done is not None and r.t_done <= w1
               and (len(r.req.tokens) != r.spec.gen_len
                    or not all(0 <= t < vocab for t in r.req.tokens)))


def _padded(r, seq_len: int, max_gen: int):
    served = list(r.req.tokens)
    toks = np.zeros((seq_len,), np.int32)
    seq = np.concatenate([r.spec.prompt, np.asarray(served[:-1], np.int32)])
    toks[:len(seq)] = seq
    pos = np.full((max_gen,), r.plen - 1, np.int32)
    pos[:len(served)] = r.plen - 1 + np.arange(len(served))
    return toks, pos, np.asarray(served)


@jax.jit
def kept_threshold(logits, temperature, top_k, top_p):
    """Per row of logits [n, V], the least logit that top-k (on the
    logits) and top-p (on the softmax at the temperature) both keep, ties
    kept: the minimal descending prefix whose mass reaches top_p."""
    desc = -jnp.sort(-logits, axis=-1)
    n, V = desc.shape
    k = jnp.clip(top_k, 1, V)
    thr_k = jnp.where(top_k > 0, desc[:, k - 1], -jnp.inf)
    p = jax.nn.softmax(desc / temperature, axis=-1)
    last = jnp.argmax(jnp.cumsum(p, axis=-1) >= top_p, axis=-1)
    thr_p = jnp.where(top_p < 1.0, desc[jnp.arange(n), last], -jnp.inf)
    return jnp.maximum(thr_k, thr_p)


def sample_kept(q, s, rng) -> np.ndarray:
    """Tokens drawn from logits q [n, V] as the program samples: Gumbel-max
    over the temperature-scaled logits the filter keeps."""
    thr = np.asarray(kept_threshold(jnp.asarray(q), s.temperature, s.top_k,
                                    s.top_p))
    g = rng.gumbel(size=q.shape).astype(np.float32)
    z = np.where(q >= thr[:, None], q / s.temperature + g, -np.inf)
    return z.argmax(axis=-1)


def gaps(ref, w, hf: dict, engine: dict, sample, *, control: bool = False,
         seed: int = 0) -> Dict[str, List[List[float]]]:
    """Per request of the sample, the gap at each served token (the logit
    gap for a greedy request, the mask gap for a sampled one); with
    control, also the gap of the int8 reference's own token at the same
    positions."""
    hp = ref.hparams_of(hf)
    seq_len = engine["prompt_len"] + engine["max_gen"]
    out = {"program": [], "control": []}
    V = hf["vocab_size"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    for r in sample:
        toks, pos, served = _padded(r, seq_len, engine["max_gen"])
        n, rows = len(served), np.arange(len(served))
        lg = np.asarray(ref.logits_at(w, toks, pos, hp))[:n, :V]
        s = r.spec.sampling
        if s is None:
            edge = lg.max(axis=-1)
        else:
            edge = np.asarray(kept_threshold(jnp.asarray(lg), s.temperature,
                                             s.top_k, s.top_p))
        gap = lambda tok: np.maximum(edge - lg[rows, tok], 0.0).tolist()
        out["program"].append(gap(served))
        if control:
            q = np.asarray(ref.logits_at(w, toks, pos, hp,
                                         quant=True))[:n, :V]
            out["control"].append(gap(q.argmax(axis=-1) if s is None
                                      else sample_kept(q, s, rng)))
    return out


def statistics(rows: List[List[float]], kind: str = "greedy"
               ) -> Dict[str, float]:
    """The gap statistics a limit can be set on (inf: nothing compared)."""
    name = GAP[kind]
    flat = [x for row in rows for x in row]
    if not flat:
        return {f"widest_{name}_gap": float("inf"),
                f"mean_{name}_gap": float("inf")}
    return {f"widest_{name}_gap": max(flat),
            f"mean_{name}_gap": sum(flat) / len(flat)}


def readings(ref, recs, w1: float, w, hf: dict, seed: int, *,
             control: bool = False, kinds=KINDS) -> Dict[str, dict]:
    """For each kind of request, the sample's size and the statistics of
    its gaps for the program and, with control, for the control."""
    c, e = hf["check"], hf["engine"]
    out = {}
    for kind in kinds:
        sample = pick(recs, w1, seed, c["sample_tokens"], c["max_requests"],
                      kind)
        g = gaps(ref, w, hf, e, sample, control=control, seed=seed)
        out[kind] = {"requests": len(sample),
                     "tokens": sum(len(x) for x in g["program"]),
                     "program": statistics(g["program"], kind),
                     "program_per_request": [max(x) for x in g["program"]]}
        if control:
            out[kind]["control"] = statistics(g["control"], kind)
    return out


def judge(ref, recs, w1: float, w, hf: dict, seed: int,
          limits: Dict[str, float]) -> Dict[str, dict]:
    """The numbers compared, each with its limit; all must hold. Only the
    kinds of request that `limits` names a statistic of are read."""
    c = hf["check"]
    kinds = [k for k in KINDS if any(f"_{GAP[k]}_" in n for n in limits)]
    got = readings(ref, recs, w1, w, hf, seed, kinds=kinds)
    st = {n: v for k in kinds for n, v in got[k]["program"].items()}
    out = {name: {"value": st[name], "limit": lim}
           for name, lim in limits.items()}
    out["wrong_length_requests"] = {
        "value": wrong_length(recs, w1, hf["vocab_size"]), "limit": 0}
    for kind in kinds:
        out[f"{kind}_tokens"] = {"value": got[kind]["tokens"],
                                 "limit": c["min_compared_tokens"]}
    return out


def holds(numbers: Dict[str, dict]) -> bool:
    """The token counts are floors; the others are ceilings."""
    ok = True
    for name, v in numbers.items():
        if v["limit"] is None:
            return False
        ok &= (v["value"] >= v["limit"] if name.endswith("_tokens")
               else v["value"] <= v["limit"])
    return bool(ok)
