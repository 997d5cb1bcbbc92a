"""Training through `Trainer.fit`'s own loop: free-running dispatch, the
packed batches through the normal data path and prefetcher, a loss fetched
only at the trainer's log steps. The window opens at the first such fetch
after the warm-up steps and closes at the last one inside `--seconds`.

The benchmark makes the weights (a CLM whose `init_params` fills the
program's tree from the seed) and the data (a DummyDataModule whose `collate`
packs each row from the traffic file's documents, in an order the seed
permutes); every row holds the same documents, so no seed changes the work.

The first `check.steps` steps of this same fit are what `correct` compares:
their losses, the first clipped gradient per leaf read back from AdamW's
first moment after step one, and the parameters' change per leaf after the
last, against the float32 reference run after the fit's state is freed."""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks import common

TRACED_SECONDS = 12.0  # of the window, what the profiler sees in a --trace 1 run


def packed_datamodule(traffic: dict, vocab: int, seed: int):
    from llm_training_tpu.data.dummy import DummyDataModule, DummyDataModuleConfig

    documents = list(traffic["documents"])
    seq = traffic["seq_len"]
    if sum(documents) != seq:
        raise SystemExit(f"documents {documents} do not fill a row of {seq}")

    class PackedDummyDataModule(DummyDataModule):
        def setup(self) -> None:
            super().setup()
            rng = np.random.default_rng((self.config.seed, 1))
            orders = np.stack(
                [rng.permutation(len(documents)) for _ in range(len(self.train_dataset))]
            ).astype(np.int32)
            # each example carries its own document order behind its tokens
            self.train_dataset = np.concatenate([self.train_dataset, orders], axis=1)

        def collate(self, examples):
            rows = np.stack(examples)
            input_ids, orders = rows[:, :seq], rows[:, seq:]
            segment_ids = np.zeros_like(input_ids)
            position_ids = np.zeros_like(input_ids)
            for r, order in enumerate(orders):
                at = 0
                for number, d in enumerate(order, start=1):
                    n = documents[d]
                    segment_ids[r, at:at + n] = number
                    position_ids[r, at:at + n] = np.arange(n)
                    at += n
            return {
                "input_ids": input_ids, "labels": input_ids.copy(),
                "segment_ids": segment_ids, "position_ids": position_ids,
            }

    rows = traffic["global_batch_rows"]
    return PackedDummyDataModule(DummyDataModuleConfig(
        batch_size=rows, max_length=seq, num_samples=rows * traffic["distinct_batches"],
        vocab_size=vocab, seed=seed % (2**31 - 1),
    ))


def seeded_objective(cell, seed: int):
    import jax

    from llm_training_tpu.lms.clm import CLM, CLMConfig
    from llm_training_tpu.optim.builder import OptimConfig

    train = cell.config["train"]
    std = cell.config["initializer_range"]

    class SeededCLM(CLM):
        def init_params(self, rng, batch):
            abstract = jax.eval_shape(self.model.init, rng, batch["input_ids"][:1])
            return common.seeded_tree(rng, abstract, std)  # rng: the run's key, an argument

    config = CLMConfig(optim=OptimConfig(**train["optim"]), ce_chunk_size=train["ce_chunk_size"])
    return SeededCLM(config, model=common.build_model(cell.config))


def find_first_moment(opt_state):
    """AdamW's first moment, wherever the optimizer chain keeps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = find_first_moment(part)
            if found is not None:
                return found
    return None


def loop_state(trainer):
    """The fit loop's live state, read out of the frame that called the
    callback: `on_train_step` is handed the step number only (PERF.md,
    section 7). A loop that no longer keeps it under this name ends the run."""
    frame = sys._getframe(2)
    state = frame.f_locals.get("state")
    if not (hasattr(state, "params") and hasattr(state, "opt_state")):
        raise SystemExit(
            f"no train state named `state` in {frame.f_code.co_name} "
            f"({frame.f_code.co_filename}): the check cannot read the fit's first steps"
        )
    if find_first_moment(state.opt_state) is None:
        raise SystemExit("the optimizer's state holds no first moment (`mu`) to read the gradient from")
    return state


def seeded_state(trainer, objective, datamodule, seed: int):
    """The fit's starting state, made on the device in one jitted call whose
    text does not hold the seed (the trainer's own initialiser bakes
    `seed + 1` into its program, which would compile anew for every seed):
    the trainer's mesh, optimizer and state shardings, the key an argument."""
    import flax.linen as nn
    import jax

    from llm_training_tpu.parallel.mesh import build_mesh
    from llm_training_tpu.trainer.state import TrainState
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    datamodule.setup()
    sample = next(datamodule.train_batches(start_step=0))
    trainer.mesh = build_mesh(trainer.config.mesh, trainer.devices)
    with trainer.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        tx, _ = trainer._build_tx(objective)
        shardings = trainer._state_shardings(trainer._abstract_state(objective, sample, tx))

        def make_state(key):
            params = objective.init_params(key, sample)
            return nn.meta.unbox(TrainState.create(params, trainer._opt_init(tx, params), key))

        return jax.jit(make_state, out_shardings=shardings)(common.base_key(seed))


class Window:
    """Trainer callback: the check's readings during warm-up, then the
    window's fetch times."""

    def __init__(self, cell, seed, seconds, trace_dir):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace_dir = trace_dir
        self.check_steps = cell.traffic["check"]["steps"]
        self.warmup_steps = max(cell.traffic["warmup_steps"], self.check_steps)
        self.losses, self.grad_norms, self.change_norms = [], None, None
        self.fetches: list[tuple] = []  # (time, step, consumed_tokens, data_wait_s, loss finite)
        self.t_open = self.setup_s = self.t_trace_stop = None
        self.tracing = False
        self.compiles = common.CompileCounter()
        self.setup_compiles = None

    # every optimizer step, no sync: keep device scalars only
    def on_train_step(self, trainer, step):
        if step > self.check_steps:
            return
        import jax
        import jax.numpy as jnp

        state = loop_state(trainer)
        self.losses.append(trainer.last_metrics["loss"])
        b1 = self.cell.config["train"]["optim"]["optimizer_kwargs"]["b1"]
        if step == 1:
            mu = find_first_moment(state.opt_state)
            self.grad_norms = jax.jit(lambda t: jax.tree.map(
                lambda m: jnp.sqrt(jnp.sum(jnp.square(m))) / (1.0 - b1), t))(mu)
        if step == self.check_steps:
            std = self.cell.config["initializer_range"]

            def change(key, params):
                def one(path, leaf):
                    start = common.seeded_leaf(key, common.path_str(path), leaf.shape, leaf.dtype, std)
                    return jnp.sqrt(jnp.sum(jnp.square(leaf - start)))
                return jax.tree_util.tree_map_with_path(one, params)

            self.change_norms = flat(jax.jit(change)(common.base_key(self.seed), state.params))

    # log steps, after the trainer's own host fetch
    def on_step_end(self, trainer, step, metrics):
        import jax

        now = time.perf_counter()
        if step < self.warmup_steps:
            return
        data_wait = trainer.ledger.summary()["goodput/data_wait_s"]
        if self.t_open is None:
            self.setup_compiles = self.compiles.mark()
            common.quiet_host()
            now = time.perf_counter()
            self.t_open = now
            self.setup_s = now - common.T_PROCESS_START
            if self.trace_dir is not None:
                self._profile = common.profiled(self.trace_dir)
                self._profile.__enter__()
                self.tracing = True
                self.t_open = now = time.perf_counter()
        self.fetches.append((now, step, trainer.counters["consumed_tokens"], data_wait,
                             bool(np.isfinite(metrics["loss"]))))
        if self.tracing and now - self.t_open >= min(TRACED_SECONDS, self.seconds):
            self._profile.__exit__(None, None, None)
            self.tracing = False
            self.t_trace_stop = now
        if now - self.t_open >= self.seconds:
            trainer.should_stop = True

    def teardown(self):
        if self.tracing:
            self._profile.__exit__(None, None, None)
            self.tracing = False


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """Worst leaf of |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    median = float(np.median(list(reference.values())))
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(float(program[name]) - float(ref)) / max(float(ref), median)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def flat(tree) -> dict:
    import jax

    return {common.path_str(p): v for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def reference_readings(cell, seed, batches, quant_name="none") -> dict:
    """The float32 reference over the same first steps, its state sharded
    over the run's chips on a mesh of its own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.references import _common as ref_common

    reference = cell.module("references", cell.config["reference"])
    devices = jax.devices()[: cell.chips] if jax.devices()[0].platform == "tpu" else jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    model = common.build_model(cell.config)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    import flax.linen as nn

    abstract = nn.meta.unbox(abstract)["params"]

    def sharding(leaf):
        spec = [None] * len(leaf.shape)
        sizes = [d if d % n == 0 else 0 for d in leaf.shape]
        if max(sizes, default=0) > 0:
            spec[int(np.argmax(sizes))] = "x"
        return NamedSharding(mesh, P(*spec))

    p_sh = jax.tree.map(sharding, abstract)
    std = cell.config["initializer_range"]

    def make(key):
        def fill(path, leaf):
            name = "params/" + common.path_str(path)
            return common.seeded_leaf(key, name, leaf.shape, jnp.float32, std)
        return jax.tree_util.tree_map_with_path(fill, abstract)

    make = jax.jit(make, out_shardings=p_sh)
    key = common.base_key(seed)
    b_sh = NamedSharding(mesh, P(None, "x", None))  # [blocks, rows, seq]
    host_batches = [{k: v for k, v in b.items() if k != "labels"} for b in batches]
    optim = dict(cell.config["train"]["optim"]["optimizer_kwargs"])
    optim["learning_rate"] = cell.config["train"]["optim"]["learning_rate"]
    optim["grad_clip_norm"] = cell.config["train"]["optim"]["grad_clip_norm"]
    out = reference.train_steps(
        lambda: make(key), cell.config, optim, host_batches,
        cell.traffic["check"]["reference_rows_per_block"],
        ref_common.QUANTS[quant_name], (p_sh, b_sh),
    )
    return {
        "losses": out["losses"],
        "grad_norms": {"params/" + k: v for k, v in flat(out["grad_norms"]).items()},
        "change_norms": {"params/" + k: v for k, v in flat(out["change_norms"]).items()},
    }


def compare(program: dict, reference: dict, limits: dict) -> tuple[bool, dict, list[str]]:
    """Each number compared, beside its limit."""
    gaps, lines = {"loss_abs": 0.0}, []
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]), start=1):
        gaps["loss_abs"] = max(gaps["loss_abs"], abs(a - b))
        lines.append(f"check loss_step{i}: program={a:.6f} reference={b:.6f} gap={abs(a - b):.3g} limit={limits['loss_abs']}")
    for key, name in (("grad_norms", "first_grad_norm_rel"), ("change_norms", "param_change_norm_rel")):
        gaps[name], where = worst_leaf_gap(program[key], reference[key])
        lines.append(f"check {name}: worst_leaf_gap={gaps[name]:.4g} at {where} limit={limits[name]}")
    return all(gaps[k] <= limits[k] for k in gaps), gaps, lines


def run(cell, seed: int, seconds: float, trace: bool, require_tpu: bool = True) -> dict:
    import jax

    from llm_training_tpu.parallel.mesh import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    device = common.device_record(cell.chips, require_tpu)
    common.configure_cache()
    train = cell.config["train"]
    datamodule = packed_datamodule(cell.traffic, cell.config["vocab_size"], seed)
    objective = seeded_objective(cell, seed)
    trace_dir = cell.root / ".bench_trace" / cell.name if trace else None
    window = Window(cell, seed, seconds, trace_dir)
    devices = jax.devices()[: cell.chips] if require_tpu else None
    mesh = dict(train["mesh"])
    if not require_tpu:
        mesh["fsdp_size"] = min(mesh.get("fsdp_size", 1), len(jax.devices()))
        devices = jax.devices()[: mesh["fsdp_size"]]
    trainer = Trainer(
        TrainerConfig(
            max_steps=10**6, seed=0, log_every_n_steps=train["log_every_n_steps"],
            mesh=MeshConfig(**mesh),
        ),
        callbacks=[window], devices=devices,
    )
    state = trainer.fit(
        objective, datamodule, state=seeded_state(trainer, objective, datamodule, seed)
    )
    window_compiles = window.compiles.compiles - window.setup_compiles[0]
    if window_compiles:
        raise SystemExit(f"{window_compiles} program(s) compiled inside the window")
    program = {
        "losses": [float(x) for x in jax.device_get(window.losses)],
        "grad_norms": flat(jax.device_get(window.grad_norms)),
        "change_norms": jax.device_get(window.change_norms),
    }
    del state
    trainer.abstract_state = None
    n_chips = len(devices) if devices else len(jax.devices())
    memory_peak = common.memory_peak_bytes(n_chips)

    fetches = [f for f in window.fetches if f[0] - window.t_open <= seconds]
    first, last = fetches[0], fetches[-1]
    span = last[0] - first[0]
    tokens = last[2] - first[2]
    traced = [f for f in fetches if window.t_trace_stop is None or f[0] <= window.t_trace_stop]
    counters = {
        "compile_s": window.setup_compiles[1], "compiles": window.setup_compiles[0],
        "cache_hits": window.setup_compiles[2],
        "steps": last[1] - first[1], "fetches": len(fetches), "tokens": tokens,
        "window_s": span, "data_wait_s": last[3] - first[3],
        "rows_per_chip": cell.traffic["global_batch_rows"] // n_chips,
    }
    if trace and len(traced) > 1:
        counters["traced_tok_s_chip"] = (
            (traced[-1][2] - traced[0][2]) / (traced[-1][0] - traced[0][0]) / n_chips
        )
    common.log("counters", counters)
    failed = sum(1 for f in window.fetches if not f[4])

    t_check = time.perf_counter()
    batches = []
    stream = datamodule.train_batches(start_step=0)
    for _ in range(window.check_steps):
        batches.append(next(stream))
    reference = reference_readings(cell, seed, batches)
    correct, gaps, lines = compare(program, reference, cell.config["check"])
    for line in lines:
        common.log(line)
    common.log(f"check reference_s={time.perf_counter() - t_check:.1f}")

    def control(*names) -> dict:
        """readings.py and the tests: the reference computed again in a lower
        precision, in the program's place, and held to the same limits."""
        out = {}
        for name in names:
            _, control_gaps, lines = compare(
                reference_readings(cell, seed, batches, name), reference, cell.config["check"]
            )
            out.update({f"control_{name}_{k}": v for k, v in control_gaps.items()})
            for line in lines:
                common.log(line.replace("check ", f"control_{name} ", 1))
        return out

    device["memory_peak_bytes"] = memory_peak
    # a readings process asks for no window: one fetch, nothing to divide
    rate = tokens / span / n_chips if span > 0 else float("nan")
    measured = {"train_tok_s_chip": rate, "setup_s": window.setup_s}
    return {
        "correct": correct and failed == 0, "attempted": counters["steps"], "failed": failed,
        "measured": measured, "counters": counters, "device": device, "trace_dir": trace_dir,
        "readings": gaps, "control": control,
    }
