"""What PR 37's builder ran on the chip for Solar-Open2-250B beside the
benchmark's cell (PERF.md section 6):

    chiprun -- python3 scripts/kda_on_chip.py
    chiprun -- python3 scripts/kda_on_chip.py --round-state bfloat16

The configuration's published widths with 2 layers (one gated GQA, one KDA;
``--layers``), float32 at the highest matmul precision: a prompt prefilled
in two slices (1024 + 512) through the page pool and the state pool, then 8
paged decode steps, every row's logits against the plain reference's full
forward (sequential recurrence, an expert at a time) on the same seeded
weights. This is what shows that the program's mathematics is the model's;
the benchmark's cell then runs it in bfloat16.

``--round-state`` is the control: the KDA state is rounded to that type
after every call, as a pool that kept it so would, and the same comparison
has to read far above the sound one. The cell's ``correct`` compares served
tokens and cannot tell the two apart (PERF.md section 7), so a change to
how ``ops/kda.py`` keeps its state shows both readings of this script, on
the parent and on itself. ``--tiny`` rehearses on the CPU at test sizes."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PAGE = 16


def _model(tiny: bool, layers: int, dtype: str):
    from benchmarks import common
    from benchmarks.runners import serve_kda

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "solar-open2-250b.json"))
    config = {**config, "torch_dtype": dtype,
              "serve": {**config["serve"], "num_hidden_layers": layers}}
    if tiny:
        config.update(
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, moe_intermediate_size=32, vocab_size=512,
            n_routed_experts=4, num_experts_per_tok=4,
            linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                                "num_heads": 4, "num_kv_heads": None},
            published={"n_routed_experts": 16},
            assumed_sizes={**config["assumed_sizes"], "kda_gate_rank": 16})
    model = serve_kda.model_dict(config, "serve")
    return model, serve_kda.program_config(model)


def agree(args, model, cfg, weights, ref, leaf: str) -> dict:
    """The comparison, for any family whose recurrent state is the pool's
    leaf ``leaf`` (scripts/gdn_on_chip.py runs it over its own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oim_tpu.models import generate as gen

    first, rest, steps, slots = (48, 24, 8, 3) if args.tiny else (1024, 512, 8, 4)
    seq = 128 if args.tiny else 2048
    params = weights.make_on_device(args.seed, model)
    pool = {**gen.init_page_pool(cfg, slots * seq // PAGE + 1, PAGE),
            **gen.init_state_pool(cfg, slots)}
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(0, cfg.vocab, first + rest + steps)
    tables = np.zeros((slots, seq // PAGE), np.int32)
    tables[1] = 1 + np.arange(seq // PAGE)

    def rounded(pool):
        if not args.round_state:
            return pool
        return {**pool, leaf: pool[leaf].astype(args.round_state)
                .astype(pool[leaf].dtype)}

    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(
            lambda p, t, n, pool, table, start: gen.prefill_into_pages(
                p, t, n, pool, table, start, cfg, PAGE, None, jnp.int32(1)),
            donate_argnums=(3,))
        decode = jax.jit(
            lambda p, t, pool, tb, pos: gen.decode_step(
                p, t, pool, tb, pos, cfg, PAGE), donate_argnums=(2,))
        got = []
        at = 0
        for n in (first, rest):
            logits, pool = prefill(
                params, jnp.asarray(tokens[None, at:at + n], jnp.int32),
                jnp.int32(n), pool, jnp.asarray(tables[1]), jnp.int32(at))
            pool = rounded(pool)
            at += n
        got.append(logits)
        for i in range(steps):
            fed = np.zeros((slots,), np.int32)
            fed[1] = tokens[at + i]
            pos = np.zeros((slots,), np.int32)
            pos[1] = at + i
            logits, pool = decode(params, jnp.asarray(fed), pool,
                                  jnp.asarray(tables), jnp.asarray(pos))
            pool = rounded(pool)
            got.append(logits[1])
        got = np.asarray(jnp.stack(got))
    del params, pool
    rows = np.arange(at - 1, at + steps)
    want = np.asarray(ref.logits_many(
        args.seed, model, [tokens.tolist()], [rows])[0])
    return {"what": "agree", "seed": args.seed, "pattern": cfg.pattern,
            "state_rounded_to": args.round_state or None,
            "device": jax.devices()[0].device_kind,
            "rows": len(rows), "logit_rms": float(np.sqrt(np.mean(want ** 2))),
            "worst_abs_difference": float(np.abs(got - want).max()),
            "argmax_agree": int((got.argmax(-1) == want.argmax(-1)).sum())}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--round-state", default="",
                   help="round the KDA state to this type after every call")
    p.add_argument("--seed", type=int, default=20260930)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    from oim_tpu.cli.common import init_jax

    init_jax("cpu" if args.tiny else "tpu")
    from benchmarks import weights_solar_open2 as weights
    from benchmarks.reference import solar_open2_like as ref

    model, cfg = _model(args.tiny, args.layers, "float32")
    print("KDA " + json.dumps(agree(args, model, cfg, weights, ref,
                                    cfg.kda.state_leaf)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
