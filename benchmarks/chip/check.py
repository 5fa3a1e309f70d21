"""The comparison that decides ``correct`` for a served model.

After the window closes, a sample of finished requests (the longest among
them, and others drawn from the seed) is run through the float32 reference
once, teacher-forced on each prompt followed by its served tokens.  At every
position that produced a served token, the gap is the reference's best logit
minus the reference's logit of the served token: 0 where the program picked
the reference's argmax, small where it picked a near tie.  The run is
correct when the widest gap is at most the configuration's limit.

``control`` reads the same gaps for the tokens that the float8 control would
have picked at the same positions: the limit has to separate the two.

Routed models.  A top-k router is a discrete choice: where the reference's
expert scores lie close together, the served precision's rounding flips
which experts a token takes, and the logits there move by far more than
rounding.  A configuration whose family marks routing near-ties
(``hidden_ties``, ``chip.family``) sets two numbers under ``check``, each
with its ``_why``: ``route_margin``, the margin delta within which the
reference's own choice of an expert held on this chip counts as a near-tie,
and ``max_route_tie_share``, the largest share of the served positions that
may be so marked.  The widest gap is then read over the positions left
unmarked, for the served tokens and the control's picks alike, at the same
limit, and the marked share is compared with its own limit.  Without the
keys every served position is compared, as above.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chip import family
from chip.shapes import Shape

HEAD_CHUNK = 128                # positions per block of LM-head logits
ROUTE_KEYS = ("route_margin", "route_margin_why", "max_route_tie_share",
              "max_route_tie_share_why")


@dataclasses.dataclass(frozen=True)
class Served:
    prompt: np.ndarray          # (P,) int32
    tokens: np.ndarray          # (n,) int32, the served tokens in order


def sample(finished: Sequence[Served], k: int, seed: int) -> List[Served]:
    """The longest finished request and ``k - 1`` others drawn from the seed."""
    if not finished:
        return []
    size = [len(r.prompt) + len(r.tokens) for r in finished]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 0xC0DE])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [finished[longest]] + [finished[rest[i]] for i in sorted(pick)]


def pack(reqs: Sequence[Served], length: int):
    """Tokens (B, length), targets and mask of the positions whose next
    token was served."""
    b = len(reqs)
    tokens = np.zeros((b, length), np.int32)
    targets = np.zeros((b, length), np.int32)
    mask = np.zeros((b, length), bool)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.tokens]).astype(np.int32)
        if len(seq) > length:
            raise ValueError(f"request of {len(seq)} tokens exceeds the "
                             f"reference length {length}")
        tokens[i, :len(seq)] = seq
        targets[i, :len(seq) - 1] = seq[1:]
        p = len(r.prompt)
        mask[i, p - 1:len(seq) - 1] = True
    return tokens, targets, mask


def route_margin(limits: dict, s: Shape) -> Optional[float]:
    """The margin delta that a configuration's ``check`` (``limits``) sets, or
    None where it sets none.  Refuses part of the rule without the rest,
    and a margin for a family that marks no ties."""
    given = [k for k in ROUTE_KEYS if k in limits]
    if not given:
        return None
    if len(given) < len(ROUTE_KEYS):
        raise ValueError(f"check: {given} need "
                         f"{[k for k in ROUTE_KEYS if k not in given]} too")
    if not hasattr(family.of(s), "hidden_ties"):
        raise ValueError(f"check: route_margin is set, but the family "
                         f"{s.family!r} marks no routing ties (no "
                         f"hidden_ties)")
    margin, share = float(limits["route_margin"]), \
        float(limits["max_route_tie_share"])
    if not (margin > 0 and 0 <= share < 1):
        raise ValueError(f"check: route_margin {margin} must be above 0 and "
                         f"max_route_tie_share {share} in [0, 1)")
    return margin


@functools.partial(jax.jit, static_argnames=("s", "control", "margin"))
def _gaps(params, tokens, targets, mask, s: Shape, control: bool,
          margin: Optional[float] = None):
    with jax.default_matmul_precision("highest"):
        fam = family.of(s)
        if margin is None:
            h_ref, ties = fam.hidden(params, tokens, s), None
        else:
            h_ref, ties = fam.hidden_ties(params, tokens, s, margin)
            mask = mask & ~ties
        h_ctl = fam.hidden(params, tokens, s, quant=True) if control else h_ref
        table = fam.head(params, s)                        # (V, d) float32
        b, t, d = h_ref.shape
        nc = t // HEAD_CHUNK

        def blocks(x):
            return x.reshape(b, nc, HEAD_CHUNK, *x.shape[2:]).swapaxes(0, 1)

        def body(_, xs):
            hr, hc, tg, mk = xs
            lr = hr @ table.T                                  # (B, C, V)
            best = lr.max(-1)
            gap = best - jnp.take_along_axis(lr, tg[..., None], -1)[..., 0]
            if control:
                top = jnp.argmax(hc @ table.T, -1)
                cgap = best - jnp.take_along_axis(lr, top[..., None], -1)[..., 0]
            else:
                cgap = gap
            return None, (jnp.where(mk, gap, 0.0), jnp.where(mk, cgap, 0.0))

        _, (gap, cgap) = jax.lax.scan(
            body, None, (blocks(h_ref), blocks(h_ctl), blocks(targets),
                         blocks(mask)))
        return (gap.swapaxes(0, 1).reshape(b, t),
                cgap.swapaxes(0, 1).reshape(b, t), ties)


@dataclasses.dataclass(frozen=True)
class Gaps:
    max_gap: float              # widest gap of the served tokens (unmarked)
    control_gap: Optional[float]  # widest gap of the float8 control's picks
    tokens: int                 # served tokens (positions) in the sample
    ties: int                   # of them, marked as routing near-ties
    requests: int

    @property
    def tie_share(self) -> Optional[float]:
        return self.ties / self.tokens if self.tokens else None


def gaps(params, reqs: Sequence[Served], s: Shape, length: int,
         control: bool = False, margin: Optional[float] = None) -> Gaps:
    """Reference gaps of ``reqs`` (padded to ``length``, a multiple of
    ``HEAD_CHUNK``) under the served weights ``params``; with ``margin``,
    over the positions the family does not mark as routing near-ties."""
    tokens, targets, mask = pack(reqs, length)
    gap, cgap, ties = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets),
                            jnp.asarray(mask), s, control, margin)
    gap, cgap = np.asarray(gap), np.asarray(cgap)
    ties = 0 if ties is None else int((np.asarray(ties) & mask).sum())
    return Gaps(max_gap=float(gap.max()),
                control_gap=float(cgap.max()) if control else None,
                tokens=int(mask.sum()), ties=ties, requests=len(reqs))


def reference_length(mix: dict) -> int:
    """Padded sequence length of the reference: the mix's longest prompt and
    answer, rounded up to a whole block of the LM head."""
    n = mix["prompt"]["max"] + mix["output"]["max"]
    return -(-n // HEAD_CHUNK) * HEAD_CHUNK
