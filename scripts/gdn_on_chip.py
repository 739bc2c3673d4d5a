"""What PR 42's builder ran on the chip for GigaChat3.5-432B-A28B beside the
benchmark's cell (PERF.md section 6):

    chiprun -- python3 scripts/gdn_on_chip.py
    chiprun -- python3 scripts/gdn_on_chip.py --round-state bfloat16

The configuration's published widths over published layers 0 and 3 (every
kind of block once: GatedDeltaNet, dense FFN, gated latent attention, an
expert block; ``--layers``), float32 at the highest matmul precision: a
prompt prefilled in two slices (1024 + 512) through the latent page pool and
the state pool, then 8 paged decode steps, every row's logits against the
plain reference's full forward (sequential recurrence, expanded attention on
interleaved rope, an expert at a time) on the same seeded weights. This is
what shows that the program's mathematics is the model's; the benchmark's
cell then runs it in bfloat16.

``--round-state`` is the control: the GatedDeltaNet state is rounded to that
type after every call, as a pool that kept it so would, and the same
comparison has to read far above the sound one (the cell's ``correct``
compares served tokens and cannot tell the two apart). The comparison itself
is scripts/kda_on_chip.py's. ``--tiny`` rehearses on the CPU at test sizes
(pipe its output through ``grep ^GDN``)."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model(tiny: bool, layers: list, dtype: str):
    from benchmarks import common
    from benchmarks.runners import serve_gdn

    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "gigachat35-432b-a28b.json"))
    config = {**config, "torch_dtype": dtype,
              "serve": {**config["serve"], "num_hidden_layers": len(layers),
                        "layers_held": layers}}
    if tiny:
        config.update(
            hidden_size=64, intermediate_size=96, num_attention_heads=4,
            moe_intermediate_size=32, vocab_size=512, n_routed_experts=4,
            num_experts_per_tok=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
            v_head_dim=16, linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4, swiglu_limit=1,
            rope_scaling={**config["rope_scaling"],
                          "original_max_position_embeddings": 64},
            published={"n_routed_experts": 16})
    model = serve_gdn.model_dict(config, "serve")
    return model, serve_gdn.program_config(model)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", default="0,3",
                   help="published layers held, comma-separated")
    p.add_argument("--round-state", default="",
                   help="round the state to this type after every call")
    p.add_argument("--seed", type=int, default=20261002)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    from oim_tpu.cli.common import init_jax

    init_jax("cpu" if args.tiny else "tpu")
    import kda_on_chip
    from benchmarks import weights_gigachat35 as weights
    from benchmarks.reference import gigachat3_5_like as ref

    model, cfg = _model(
        args.tiny, [int(i) for i in args.layers.split(",")], "float32")
    print("GDN " + json.dumps(kda_on_chip.agree(
        args, model, cfg, weights, ref, cfg.gdn.state_leaf)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
