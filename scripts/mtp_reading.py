"""One reading, outside the benchmark: the multi-token-prediction module of a
`Deepseek` configuration file at its published widths (`num_nextn_predict_layers`
1, which the served configuration cuts to 0), the program's logits in the
stated precision against the plain reference's in float32, on packed rows of
seeded tokens. Needs the chip unless `--cpu` (a rehearsal at a tiny size).

    python scripts/mtp_reading.py --config benchmarks/configs/openpangu-ultra-moe-718b-ep32.json \\
        --rows 4 --length 2048 --seed 2411000043

Prints one JSON line: for the main logits and for the module's, over the
positions that have a target inside their document, the largest absolute
difference, and how far the reference's logit of the program's first choice
lies below the reference's best (the widest, and the share over 0.3)."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--rows", type=int, default=4)
    parser.add_argument("--length", type=int, default=2048)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--cpu", action="store_true", help="a rehearsal: no chip asked for")
    args = parser.parse_args(argv)

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common

    config = json.loads(Path(args.config).read_text())
    device = common.device_record(1, require_tpu=not args.cpu)
    common.configure_cache()
    model = common.build_model(config, {"num_nextn_predict_layers": 1})
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    variables = jax.jit(
        lambda key: nn.meta.unbox(common.seeded_tree(key, abstract, config["initializer_range"]))
    )(common.base_key(args.seed))
    module = variables["params"]["mtp_0"]
    extra = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(module))

    # two documents a row, of 5/8 and 3/8 of it
    rng = np.random.default_rng(args.seed)
    first = args.length * 5 // 8
    ids = rng.integers(0, config["vocab_size"], size=(args.rows, args.length)).astype(np.int32)
    seg = np.tile(np.concatenate([np.full(first, 1), np.full(args.length - first, 2)]), (args.rows, 1)).astype(np.int32)
    pos = np.tile(np.concatenate([np.arange(first), np.arange(args.length - first)]), (args.rows, 1)).astype(np.int32)
    ids, seg, pos = jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos)

    out = jax.jit(lambda v: model.apply(
        v, input_ids=ids, segment_ids=seg, position_ids=pos, return_mtp=True))(variables)
    got = {"logits": out.logits.astype(jnp.float32), "mtp_logits": out.mtp_logits.astype(jnp.float32)}
    reference = common.load_module(ROOT / "benchmarks" / "references" / f"{config['reference']}.py")
    want = dict(zip(("logits", "mtp_logits"), reference.mtp_logits(variables["params"], config, ids, seg, pos)))

    shifted = lambda a, n: jnp.concatenate([a[:, n:], jnp.zeros_like(a[:, :n])], axis=1)
    result = {"device": device, "rows": args.rows, "length": args.length, "seed": args.seed,
              "module_parameters": extra, "config": Path(args.config).name}
    for ahead, name in ((1, "logits"), (2, "mtp_logits")):
        valid = (seg > 0) & (seg == shifted(seg, ahead))
        exact, mine = want[name], got[name]
        picked = mine.argmax(axis=-1)
        below = exact.max(axis=-1) - jnp.take_along_axis(exact, picked[..., None], axis=-1)[..., 0]
        below = np.asarray(below)[np.asarray(valid)]
        result[name] = {
            "positions": int(below.size),
            "largest_abs_difference": float(jnp.max(jnp.where(valid[..., None], jnp.abs(mine - exact), 0.0))),
            "largest_abs_reference_logit": float(jnp.max(jnp.where(valid[..., None], jnp.abs(exact), 0.0))),
            "widest_gap_of_first_choice": float(below.max()),
            "share_over_0.3": float((below > 0.3).mean()),
            "same_first_choice_share": float((below == 0).mean()),
        }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
