"""Serving runner for a model family the llama-like reference does not
cover: the configuration names its family (``"serve_family":
"deepseek_like"``), and with it the plain reference
(``reference/<family>.py``) and the weights (``weights_<family minus
_like>.py``) of its own tree.

Everything else IS ``runners/serve.py``: this file loads that module
afresh and calls its ``run`` with four of its collaborators exchanged —
the model's description, the weights, the warm-up (the engine prefills in
chunks here, so the shapes to warm are the chunk's buckets) and the check
against the reference. Streams, open loop, failure accounting, window and
clocks are that file's own code, not a copy of it. The engine is the
program's ``ServeEngine`` with ``prefill_chunk`` from the configuration's
``serve`` group (runners/serve.py builds its engine without one: until a
``benchmark`` PR lets it take engine arguments from the configuration, a
subclass that fixes the argument stands in for the length of the call).

From the program come besides: ``ServeEngine.stats()`` and
``pool_stats()``, sampled twice a second, for the window's means of the
expert load and of the latent pool's fill.
"""

from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
import types

import numpy as np

from benchmarks import common, traffic

FAMILIES = {"deepseek_like": "weights_deepseek"}
SAMPLE_S = 0.5


def _family(config: dict):
    """(reference module, weights module) of the configuration's family."""
    name = config.get("serve_family")
    if name not in FAMILIES:
        raise SystemExit(f"runners/serve_family.py: no family {name!r} "
                         f"(have: {sorted(FAMILIES)})")
    import importlib

    return (importlib.import_module(f"benchmarks.reference.{name}"),
            importlib.import_module(f"benchmarks.{FAMILIES[name]}"))


def model_dict(config: dict, runner: str = "serve") -> dict:
    """The published keys under the names the reference, the weights and
    the byte counts use, at the depth and context length this cell runs."""
    sizes = config[runner]
    for key in ("n_group", "topk_group"):
        if config[key] != 1:
            raise SystemExit(f"{key}={config[key]}: group-limited routing "
                             "is not implemented (program or reference)")
    if not config["norm_topk_prob"] or config["rope_scaling"] is not None \
            or config["scoring_func"] != "sigmoid" \
            or config["moe_layer_freq"] != 1 or config["attention_bias"]:
        raise SystemExit("the deepseek_like family runs sigmoid scoring with "
                         "renormalised top-k, every layer after the leading "
                         "dense ones an expert layer, no rope scaling, no bias")
    return {
        "family": config["serve_family"],
        "vocab": config["vocab_size"], "dim": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "q_lora_rank": config["q_lora_rank"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_head_dim": config["qk_nope_head_dim"],
        "qk_rope_head_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "mlp_dim": config["intermediate_size"],
        "moe_dim": config["moe_intermediate_size"],
        "n_experts": config["n_routed_experts"],
        "moe_top_k": config["num_experts_per_tok"],
        "n_shared": config["n_shared_experts"],
        "n_dense_layers": config["first_k_dense_replace"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "dtype": config["torch_dtype"],
        "n_layers": sizes["num_hidden_layers"],
        "max_seq": sizes["max_position_embeddings"],
    }


def program_config(model: dict, **extra):
    """The program's own Config for ``model``, or one line and a non-zero
    exit where the program cannot express it (a commit before latent
    attention and the dropless experts: its Config has no such field)."""
    import jax.numpy as jnp

    from oim_tpu.models import llama

    if model["rms_norm_eps"] != 1e-6:
        raise SystemExit("the program's rmsnorm epsilon is fixed at 1e-6; "
                         f"the configuration states {model['rms_norm_eps']}")
    fields = dict(
        vocab=model["vocab"], dim=model["dim"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], n_kv_heads=model["n_heads"],
        head_dim=model["dim"] // model["n_heads"], mlp_dim=model["mlp_dim"],
        max_seq=model["max_seq"], rope_theta=model["rope_theta"],
        dtype=jnp.dtype(model["dtype"]),
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], n_experts=model["n_experts"],
        moe_top_k=model["moe_top_k"], moe_dispatch="ragged",
        moe_intermediate_size=model["moe_dim"],
        n_shared_experts=model["n_shared"],
        first_k_dense_replace=model["n_dense_layers"],
        scoring_func="sigmoid", routed_scaling_factor=model["routed_scale"])
    fields.update(extra)
    try:
        return llama.Config(**fields)
    except (TypeError, ValueError) as err:
        raise SystemExit(f"the program cannot express the {model['family']} "
                         f"family: {err}") from None


def _piece_buckets(requests, chunk: int, max_seq: int, bucket) -> dict:
    """{prefill bucket: the longest piece of the window's prompts that
    falls into it}: a prompt is prefilled in pieces of ``chunk`` tokens and
    a rest, each padded to its bucket."""
    longest: dict[int, int] = {}
    for r in requests:
        n = len(r.prompt)
        pieces = [n] if not chunk or n <= chunk else (
            [chunk] + ([n % chunk] if n % chunk else []))
        for p in pieces:
            b = bucket(p, max_seq)
            longest[b] = max(longest.get(b, 0), p)
    return longest


def _sampler(engine_box: list, samples: list, stop: threading.Event):
    """Holds no reference to the engine between two samples: once the box
    is emptied the engine's memory can go to the reference."""
    while not stop.wait(SAMPLE_S):
        if engine_box:
            samples.append((time.monotonic(), engine_box[0].stats(),
                            engine_box[0].pool_stats()))


def _window_means(samples, lo: float, hi: float) -> dict:
    """The window's means from the samples inside it: expert load from the
    difference of the engine's running sums, pool fill from its census."""
    inside = [s for s in samples if lo <= s[0] <= hi]
    if len(inside) < 2:
        return {}
    out = {"latent_pool_fill": float(np.mean(
        [100.0 * p["used_pages"] / p["total_pages"] for _, _, p in inside]))}
    first, last = inside[0][1], inside[-1][1]
    steps = last.get("expert_load_steps", 0) - first.get("expert_load_steps", 0)
    if steps > 0:
        for stat, key in (("experts_touched", "experts_touched_sum"),
                          ("expert_load_max_over_mean",
                           "expert_load_max_over_mean_sum")):
            out[stat] = (last[key] - first[key]) / steps
    return out


def run(ctx: common.Context) -> dict:
    # First: can the program express this configuration at all? One line
    # and a non-zero exit if not, before any weights, engine or compile.
    model = model_dict(ctx.config, "serve")
    program_config(model)
    ref, family_weights = _family(ctx.config)
    sizes = ctx.config["serve"]
    chunk = int(sizes.get("prefill_chunk", 0))

    import oim_tpu.serve.engine as engine_module

    # This checkout's own runners/serve.py (a test's copy finds its copy).
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    base = common.plugin(root, "runners", "serve")
    engine_box: list = []

    class ChunkedEngine(engine_module.ServeEngine):
        """The program's engine with this cell's ``prefill_chunk``."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, prefill_chunk=chunk, **kwargs)
            engine_box.append(self)

    samples: list = []
    stop = threading.Event()
    sampler = threading.Thread(target=_sampler, name="bench-sampler",
                               args=(engine_box, samples, stop), daemon=True)

    def warm_up(ctx, engine, requests, vocab, max_seq):
        rng = np.random.default_rng([int(ctx.seed), 9])
        pieces = _piece_buckets(requests, chunk, max_seq, base._bucket)
        for b, n in sorted(pieces.items()):
            s = base._Stream(-1, traffic.Request(
                0.0, rng.integers(0, vocab, n, dtype=np.int32), 2))
            if not base._submit(engine, s) or not s.done.wait(1100.0) \
                    or not s.finished:
                raise SystemExit(f"warm-up of prefill bucket {b} failed: "
                                 f"{s.refused or s.handle.finish_reason}")
            ctx.log("warmed", bucket=b, prompt=n)

    def check(ctx, model, finished, limits):
        stop.set()  # the engine's memory is the reference's now
        sampler.join()
        engine_box.clear()
        gc.collect()
        return _check(ctx, ref, model, finished, limits)

    # runners/serve.py's run() with this family's collaborators: its own
    # module object (loaded afresh, so nothing another caller holds moves).
    base.common = types.SimpleNamespace(**{
        **vars(common), "model_dict": model_dict,
        "program_config": program_config})
    base.weights = family_weights
    base._warm_up = warm_up
    base._check = check

    sampler.start()
    try:
        with _engine_class(engine_module, ChunkedEngine):
            result = base.run(ctx)
    finally:
        stop.set()
        sampler.join()
    t_start = ctx.t0 + result["setup_s"]
    means = _window_means(samples, t_start, t_start + ctx.seconds)
    ctx.log("engine counters over the window", samples=len(samples), **{
        k: f"{v:.4g}" for k, v in means.items()})
    result["stats"].update(means)
    return result


@contextlib.contextmanager
def _engine_class(engine_module, subclass):
    """``ServeEngine`` as runners/serve.py will import it, for the length
    of its run: a subclass of the program's (an ``isinstance`` check still
    holds) that fixes this cell's ``prefill_chunk``."""
    real = engine_module.ServeEngine
    engine_module.ServeEngine = subclass
    try:
        yield
    finally:
        engine_module.ServeEngine = real


def _sample(ctx, finished):
    """The requests the reference judges: the longest finished one and a
    seeded sample of the others (as runners/serve.py's ``_check``)."""
    n_sample = int(ctx.traffic["check_requests"])
    by_len = sorted(finished, key=lambda s: -(len(s.req.prompt) + s.req.max_new))
    rest = by_len[1:]
    pick = np.random.default_rng([int(ctx.seed), 7]).permutation(len(rest))
    return [by_len[0]] + [rest[i] for i in pick[: n_sample - 1]]


def _check(ctx, ref, model: dict, finished, limits: dict) -> dict:
    """Served tokens against the family's float32 reference, after the
    engine is gone; the numbers compared are the widest and the mean gap by
    which a served token's reference logit lies below the reference's best."""
    if not finished:
        return {"ok": False, "numbers": {}, "sample": []}
    sample = [(s.req.prompt.tolist(), s.tokens) for s in _sample(ctx, finished)]
    t = time.monotonic()
    gaps = np.concatenate(ref.served_gaps_many(ctx.seed, model, sample))
    ctx.log("reference ran", seconds=f"{time.monotonic() - t:.1f}",
            positions=[len(p) + len(s) for p, s in sample])
    numbers, ok = _verdict(ctx, gaps, limits, len(sample))
    return {"ok": ok, "numbers": numbers, "sample": sample}


def _verdict(ctx, gaps, limits: dict, requests: int, who: str = "correct?"):
    """(numbers, within every limit?) of one set of gaps: the comparison
    that decides ``correct``, for a run's own tokens and for the control."""
    numbers = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    ok = True
    for name, limit in limits.items():  # a number without a limit is only shown
        ctx.log(who, number=name, value=f"{numbers[name]:.6g}",
                limit=limit, tokens=len(gaps), requests=requests)
        ok = ok and numbers[name] <= limit
    ctx.log("also read", **{k: f"{v:.6g}" for k, v in numbers.items()
                            if k not in limits})
    return numbers, ok


def control_check(ctx, sample) -> dict:
    """The float8 control on the sample a run judged, held to the run's own
    limits by the run's own comparison: it has to come out not correct
    (``check_limits_family.py``)."""
    ref, _ = _family(ctx.config)
    t = time.monotonic()
    gaps = np.concatenate(ref.served_gaps_many(
        ctx.seed, model_dict(ctx.config, "serve"), sample, control=True))
    ctx.log("control ran", seconds=f"{time.monotonic() - t:.1f}")
    numbers, ok = _verdict(ctx, gaps, ctx.config["serve"]["limits"],
                           len(sample), who="control correct?")
    return {"correct": ok, "numbers": numbers}
