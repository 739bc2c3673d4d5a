"""oim-infer: KV-cached generation from an oim-trainer checkpoint.

The serving half of the trainer's checkpoint contract (new scope — the
reference is a storage control plane): restore the latest step from
--checkpoint-dir, decode with models/generate.py, print token ids. Works
with raw token-id prompts (tokenization is outside this framework's
scope; pair with any tokenizer).

    oim-infer --checkpoint-dir /ckpt --model llama-tiny \
        --prompt 12,7,900 --n-new 64 --temperature 0.8
"""

from __future__ import annotations

import argparse

import numpy as np

from oim_tpu.cli.common import (
    add_common_flags,
    add_model_override_flag,
    init_jax,
    parse_model_overrides,
    restore_checkpoint_params,
    setup_logging,
)
from oim_tpu.common.logging import from_context
from oim_tpu.train import TrainConfig


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser("oim-infer")
    parser.add_argument("--checkpoint-dir", required=True)
    parser.add_argument("--model", default="llama-tiny",
                        choices=("llama-tiny", "llama-tiny-moe", "llama3-8b"))
    add_model_override_flag(parser)
    parser.add_argument("--prompt", default="",
                        help="comma-separated token ids; repeat the flag-"
                             "value with ';' between rows for a batch")
    parser.add_argument("--n-new", type=int, default=32)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--max-seq", type=int, default=0,
                        help="cache length (default: prompt + n-new)")
    parser.add_argument("--platform", default="",
                        help="jax platform, overriding JAX_PLATFORMS "
                             "(tpu | cpu)")
    add_common_flags(parser)
    args = parser.parse_args(argv)
    setup_logging(args)
    log = from_context()

    init_jax(args.platform)

    import jax
    import jax.numpy as jnp

    from oim_tpu.models import generate as gen

    mcfg = TrainConfig(
        model=args.model,
        model_overrides=parse_model_overrides(args.model_override),
    ).model_config()
    if args.prompt:
        rows = [
            [int(t) for t in row.split(",") if t.strip()]
            for row in args.prompt.split(";")
        ]
        width = len(rows[0])
        if any(len(r) != width for r in rows):
            raise SystemExit("all prompt rows must have the same length")
        prompt = jnp.asarray(rows, jnp.int32)
        if int(prompt.max()) >= mcfg.vocab:
            raise SystemExit(
                f"prompt token {int(prompt.max())} >= vocab {mcfg.vocab}"
            )
    else:
        prompt = jax.random.randint(
            jax.random.PRNGKey(args.seed), (1, 8), 0, mcfg.vocab, jnp.int32
        )

    params, step = restore_checkpoint_params(
        args.checkpoint_dir, mcfg, "sample")
    log.info("restored", step=step, model=args.model)

    out = gen.generate(
        params, prompt, args.n_new, mcfg,
        temperature=args.temperature, rng=jax.random.PRNGKey(args.seed),
        max_seq=args.max_seq or None,
    )
    for row in np.asarray(out):
        print(",".join(str(int(t)) for t in row))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
