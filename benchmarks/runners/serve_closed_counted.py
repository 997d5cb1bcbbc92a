"""`serve_closed`'s closed loop, window and widest gap as they are (this
file calls that runner's `run` and times nothing itself), with a SECOND number
held by `correct`: the share of the served tokens whose reference logit lies
more than `check.far_level` below the reference's best, against
`check.far_token_share`.

Why a cell wants it (PERF.md, section 2). Where one chip holds a share of the
experts, a router's near-tie that falls the other way under the stated
precision moves a held expert's whole contribution in or out of the share, so
the widest gap of some 14,500 served tokens is one token's accident and reads
nearly the same for bfloat16 and for the fp8 control. What differs, by an order
of magnitude, is how MANY tokens lie far off. A maximum cannot see that.

How, with nothing of `serve_closed` edited or patched: that runner asks the
cell for its reference (`cell.module("references", ...)`), and this one hands
it the cell's own reference with a counter around `logits`. The counter is
given what the reference is given (ids, segments, positions); it finds each
row's prompt by drawing the requests again from the seed, as the loop drew
them, so it knows which positions were served. Each number is computed once,
in the one pass over the reference that `serve_closed` makes anyway."""

from __future__ import annotations

import functools

import numpy as np

from benchmarks import common

KEY_TOKENS = 16  # of a prompt's first tokens, what tells requests apart
MOST_REQUESTS = 100_000


class CountedReference:
    """The cell's reference, counting as it answers. `far[name]` and
    `widest[name]` are over the served positions of every row it was asked
    about: `none` for the served tokens themselves, a control's name for the
    tokens the reference computed in that precision puts first there."""

    def __init__(self, reference, serve_closed, traffic: dict, vocab: int, seed: int, level: float):
        self.reference, self.level = reference, level
        self._request = lambda index: serve_closed.make_request(traffic, vocab, seed, index)
        self._key_tokens = min(KEY_TOKENS, *traffic["prompt_lengths"])
        self._prompt_len: dict[tuple, int] = {}
        self._drawn = 0
        self._exact = None  # the float32 logits of the rows in hand, and where they were served
        self.far: dict[str, int] = {}
        self.widest: dict[str, float] = {}
        self.tokens = 0

    def prompt_length(self, row_ids) -> int:
        key = tuple(int(t) for t in row_ids[: self._key_tokens])
        while key not in self._prompt_len:
            if self._drawn >= MOST_REQUESTS:
                raise SystemExit("a row the reference was asked about starts like no request of this seed")
            prompt = self._request(self._drawn)["prompt"]
            self._prompt_len.setdefault(tuple(prompt[: self._key_tokens]), len(prompt))
            self._drawn += 1
        return self._prompt_len[key]

    def served_positions(self, ids, seg):
        """[rows, width] bool: the positions whose logits chose a served token."""
        mask = np.zeros(ids.shape, bool)
        for row, total in enumerate((seg > 0).sum(axis=1)):
            if total:
                mask[row, self.prompt_length(ids[row]) - 1 : total - 1] = True
        return mask

    def logits(self, params, cfg, ids, seg, pos, quant):
        import jax.numpy as jnp

        from benchmarks.references import _common as ref_common

        name = next(k for k, v in ref_common.QUANTS.items() if v is quant)
        if name == "none":
            self._exact = None  # the rows before these are done with
        out = self.reference.logits(params, cfg, ids, seg, pos, quant)
        if name == "none":
            mask = self.served_positions(np.asarray(ids), np.asarray(seg))
            self._exact = (out, jnp.asarray(mask))
            self.tokens += int(mask.sum())
            picked = jnp.roll(ids, -1, axis=1)  # position p chose the token at p + 1
        else:
            picked = out.argmax(axis=-1)
        exact, mask = self._exact
        far, widest = _far_and_widest()(exact, picked, mask, self.level)
        self.far[name] = self.far.get(name, 0) + int(far)
        self.widest[name] = max(self.widest.get(name, 0.0), float(widest))
        return out


@functools.cache
def _far_and_widest():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(exact, picked, mask, level):
        below = exact.max(axis=-1) - jnp.take_along_axis(exact, picked[..., None], axis=-1)[..., 0]
        return jnp.sum(mask & (below > level)), jnp.max(jnp.where(mask, below, 0.0))

    return count


class _CellWithCounter:
    """The cell as `serve_closed` sees it: everything the cell's own, but the
    reference it is handed."""

    def __init__(self, cell, reference):
        self._cell, self._reference = cell, reference

    def __getattr__(self, name):
        return getattr(self._cell, name)

    def module(self, kind: str, name: str):
        return self._reference if kind == "references" else self._cell.module(kind, name)


def run(cell, seed: int, seconds: float, trace: bool, require_tpu: bool = True) -> dict:
    serve_closed = cell.module("runners", "serve_closed")
    check = cell.config["check"]
    counted = CountedReference(
        cell.module("references", cell.config["reference"]), serve_closed, cell.traffic,
        cell.config["vocab_size"], seed, check["far_level"],
    )
    outcome = serve_closed.run(_CellWithCounter(cell, counted), seed, seconds, trace, require_tpu)

    def share(name: str) -> float:
        return counted.far.get(name, 0) / max(counted.tokens, 1)

    readings = outcome["readings"]
    if (counted.tokens != readings["tokens_compared"]
            or abs(counted.widest.get("none", 0.0) - readings["served_logit_gap"]) > 1e-4):
        raise SystemExit(
            f"counted {counted.tokens} served tokens, widest {counted.widest.get('none')}, where serve_closed "
            f"compared {readings['tokens_compared']}, widest {readings['served_logit_gap']}: not the same positions"
        )
    readings.update(far_tokens=counted.far.get("none", 0), far_token_share=share("none"))
    limit = check["far_token_share"]
    common.log(
        f"check far_token_share={share('none'):.6g} limit={limit} far_level={check['far_level']} "
        f"far_tokens={readings['far_tokens']} tokens_compared={counted.tokens}"
    )
    outcome["correct"] = bool(outcome["correct"] and share("none") <= limit)
    widest_only = outcome["control"]

    def control(*names):
        """`serve_closed`'s readings with each control beside them, and the
        same for the second number (the sound tokens are read once more)."""
        counted.far.clear(), counted.widest.clear()
        counted.tokens = 0
        out = widest_only(*names)
        out.update(far_tokens=counted.far.get("none", 0), far_token_share=share("none"))
        out.update({f"control_{name}_far_token_share": share(name) for name in names})
        return out

    outcome["control"] = control
    return outcome
