"""What a serve cell's engine serves in its first `--steps` engine steps, token
by token: the benchmark's own seeded weights, traffic and closed loop
(`benchmarks/runners/serve_closed.py`), no timing and no reference. Two trees
on the same seed are compared by the hash of what they served (a kernel that
changes a summation order may flip a near-tie; one that changes a precision
flips many): run it from each tree's root, then `--compare` the two files.

    python scripts/served_tokens.py --workload pangu-serve-longctx8k --seed 7 --steps 200 --out a.json
    python scripts/served_tokens.py --compare parent.json change.json
    python scripts/served_tokens.py --near-ties parent.json change.json --out ties.json

`--near-ties` (on the chip: the cell's float32 reference) takes the first token
at which each differing request's two sides part and says how far apart the
REFERENCE puts the two tokens there, beside how far each lies below the
reference's best: a near-tie of the reference that bfloat16 rounding tips
reads a few hundredths; a changed precision reads tenths on many.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def serve(workload: str, seed: int, steps: int) -> dict:
    from benchmarks import common
    from benchmarks.runners import serve_closed

    cell = common.Cell(ROOT, workload)
    device = common.device_record(cell.chips, True)
    common.configure_cache()
    _, engine = serve_closed.build_engine(cell, seed)
    loop = serve_closed.Loop(cell, engine, seed)
    served: dict[str, list] = {}
    on_event = loop._on_event

    def kept(event, now):
        if event["type"] == "token":
            served.setdefault(event["id"], []).append([int(event["token"]), float(event["logprob"])])
        on_event(event, now)

    loop._on_event = kept
    for _ in range(cell.traffic["clients"]):
        loop.submit_next()
    for _ in range(steps):
        loop.step()
    for event in engine.flush():  # the tokens of the calls still in flight
        kept(event, 0.0)
    tokens = {rid: [t for t, _ in pairs] for rid, pairs in sorted(served.items())}
    stats = engine.stats()
    return {
        "workload": workload, "seed": seed, "steps": steps, "device": device,
        "requests": len(tokens), "tokens": sum(len(v) for v in tokens.values()),
        "sha256": hashlib.sha256(json.dumps(tokens, sort_keys=True).encode()).hexdigest(),
        "chunk_attention_kernel_layers": stats.get("decode/chunk_attention_kernel_layers"),
        "experts_in_place_layers": stats.get("decode/experts_in_place_layers"),
        "served": served,
    }


def compare(first: Path, second: Path) -> int:
    a, b = (json.loads(path.read_text()) for path in (first, second))
    print(f"{first}: {a['tokens']} tokens of {a['requests']} requests, sha256 {a['sha256']}")
    print(f"{second}: {b['tokens']} tokens of {b['requests']} requests, sha256 {b['sha256']}")
    if a["sha256"] == b["sha256"]:
        print("the same tokens")
        return 0
    differing = 0
    for rid in sorted(set(a["served"]) | set(b["served"]), key=lambda r: int(r[1:])):
        mine, theirs = a["served"].get(rid, []), b["served"].get(rid, [])
        at = next((i for i, (x, y) in enumerate(zip(mine, theirs)) if x[0] != y[0]), None)
        if at is None and len(mine) == len(theirs):
            continue
        differing += 1
        if at is None:
            print(f"{rid}: {len(mine)} and {len(theirs)} tokens, the shorter a prefix of the longer")
        else:
            # the first token that differs, with each side's log-probability of ITS choice: a
            # near-tie shows as two log-probabilities close together
            print(f"{rid}: first differs at token {at} of {len(mine)}/{len(theirs)}: "
                  f"{mine[at][0]} (logprob {mine[at][1]:.4f}) against {theirs[at][0]} ({theirs[at][1]:.4f})")
    print(f"{differing} of {len(set(a['served']) | set(b['served']))} requests differ")
    return 1


def near_ties(first: Path, second: Path) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common
    from benchmarks.references import _common as ref_common
    from benchmarks.runners import serve_closed

    a, b = (json.loads(path.read_text()) for path in (first, second))
    assert (a["workload"], a["seed"]) == (b["workload"], b["seed"]), "two runs of one cell and seed"
    cell = common.Cell(ROOT, a["workload"])
    device = common.device_record(cell.chips, True)
    common.configure_cache()
    model = common.build_model(cell.config)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    variables = jax.jit(
        lambda key: nn.meta.unbox(common.seeded_tree(key, abstract, cell.config["initializer_range"]))
    )(common.base_key(a["seed"]))
    reference = cell.module("references", cell.config["reference"])
    width, rows = cell.traffic["engine"]["max_model_len"], serve_closed.CHECK_ROWS
    parted = []  # (request, index of the first token that differs, the prefix both served, the two tokens)
    for rid in sorted(set(a["served"]) & set(b["served"]), key=lambda r: int(r[1:])):
        mine, theirs = a["served"][rid], b["served"][rid]
        at = next((i for i, (x, y) in enumerate(zip(mine, theirs)) if x[0] != y[0]), None)
        if at is not None:
            parted.append((rid, at, [t for t, _ in mine[:at]], (mine[at][0], theirs[at][0])))
    found = []
    for start in range(0, len(parted), rows):
        ids, seg = np.zeros((rows, width), np.int32), np.zeros((rows, width), np.int32)
        batch = parted[start : start + rows]
        where = []
        for row, (rid, at, prefix, _) in enumerate(batch):
            prompt = serve_closed.make_request(cell.traffic, cell.config["vocab_size"], a["seed"], int(rid[1:]))["prompt"]
            tokens = prompt + prefix
            ids[row, : len(tokens)], seg[row, : len(tokens)] = tokens, 1
            where.append(len(tokens) - 1)  # the logits at position p choose the token at p + 1
        pos = np.broadcast_to(np.arange(width, dtype=np.int32), ids.shape)
        logits = reference.logits(
            variables["params"], cell.config, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos),
            ref_common.QUANTS["none"],
        )
        for row, (rid, at, _, (mine, theirs)) in enumerate(batch):
            at_p = np.asarray(logits[row, where[row]], np.float32)
            found.append({
                "id": rid, "token_index": at, "first": int(mine), "second": int(theirs),
                "reference_best": float(at_p.max()), "first_below_best": float(at_p.max() - at_p[mine]),
                "second_below_best": float(at_p.max() - at_p[theirs]),
                "apart": float(abs(at_p[mine] - at_p[theirs])),
            })
            print(json.dumps(found[-1]), flush=True)
    return {"workload": a["workload"], "seed": a["seed"], "device": device, "requests": len(a["served"]),
            "parted": found, "widest_apart": max((f["apart"] for f in found), default=0.0)}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--out")
    parser.add_argument("--compare", nargs=2, type=Path)
    parser.add_argument("--near-ties", nargs=2, type=Path)
    args = parser.parse_args()
    if args.compare:
        return compare(*args.compare)
    result = near_ties(*args.near_ties) if args.near_ties else serve(args.workload, args.seed, args.steps)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result))
    print(json.dumps({k: v for k, v in result.items() if k != "served"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
