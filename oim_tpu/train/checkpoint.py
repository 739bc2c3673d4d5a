"""Checkpoint / resume (orbax-backed).

New scope relative to the reference, which persists nothing and rebuilds
state by querying the device (SURVEY.md section 5.4). The trainer keeps that
stance for *staging* state (re-query the controller) and adds durable
checkpoints only for model/optimizer state. Sharded arrays save/restore with
their shardings preserved (orbax handles jax.Array natively), so resume onto
the same mesh needs no resharding pass.
"""

from __future__ import annotations

import os
from typing import Any


class Checkpointer:
    """Thin orbax CheckpointManager wrapper: save(step, state) / restore()."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    def save(self, step: int, state: Any, wait: bool = False) -> None:
        self._mgr.save(step, args=self._ocp.args.StandardSave(state))
        if wait:
            self._mgr.wait_until_finished()

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore(self, abstract_state: Any, step: int | None = None) -> Any:
        """Restore into the structure/shardings of ``abstract_state`` (a
        matching pytree of jax.ShapeDtypeStructs or concrete arrays)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        return self._mgr.restore(
            step, args=self._ocp.args.StandardRestore(abstract_state)
        )

    def restore_params(self, abstract_params: Any) -> tuple[Any, int]:
        """(params, step): ONLY ``state.params`` of the latest step (the
        serving half of the checkpoint contract) — the optimizer moments
        never touch device memory, so a checkpoint whose trainer needed
        8 B a parameter serves from 2. A leaf whose stored shape differs
        from ``abstract_params`` (a depth cut that does not match the
        checkpoint) is an error, never a truncation."""
        import jax

        step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        # "default" is the item name CheckpointManager gives an unnamed
        # StandardSave; a fresh manager knows no handler for it until its
        # first restore, so the stored shapes are read directly.
        stored = self._ocp.PyTreeCheckpointer().metadata(os.path.join(
            self.directory, str(step), "default")).item_metadata.tree["params"]
        have = {
            jax.tree_util.keystr(path): tuple(meta.shape)
            for path, meta in jax.tree_util.tree_flatten_with_path(stored)[0]}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                abstract_params)[0]:
            name = jax.tree_util.keystr(path)
            if have.get(name) != tuple(leaf.shape):
                raise ValueError(
                    f"checkpoint step {step} holds {name} with shape "
                    f"{have.get(name)}, the configured model wants "
                    f"{tuple(leaf.shape)} (same --model and "
                    "--model-override as the trainer that wrote it?)")
        item = {"params": abstract_params}
        restored = self._mgr.restore(step, args=self._ocp.args.PyTreeRestore(
            item=item,
            restore_args=self._ocp.checkpoint_utils.construct_restore_args(
                item),
            partial_restore=True))
        return restored["params"], step

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()


def restore_llama_params(directory: str, mcfg) -> tuple[Any, int]:
    """(params, step) of the latest checkpoint in ``directory`` for the
    llama config ``mcfg``, placed on the default device — what oim-serve
    and oim-infer load. No Trainer, no optimizer state."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from oim_tpu.models import llama

    sharding = SingleDeviceSharding(jax.devices()[0])
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), mcfg)))
    ckpt = Checkpointer(directory)
    try:
        return ckpt.restore_params(abstract)
    finally:
        ckpt.close()
