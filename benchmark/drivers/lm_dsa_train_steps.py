"""Driver ``lm_dsa_train_steps``: ``lm_train_steps`` for the ``keye_vl2``
family.

The loop, its spans and counters, the state made from ``--seed`` and the
first steps the check reads are ``lm_train_steps``'s, line for line: this
file runs a copy of that module of its own in which the names that say
WHICH decoder is checked stand for this family's files — the reference
(``reference/keye_vl2_fp32.py`` where it says ``kimi_linear_fp32``: the
same ``Recipe`` / ``Shape`` / ``first_steps`` surface), the renaming of
the program's leaves into the reference's layout (``lm_dsa_weights.py``
where it says ``lm_weights``) and the check (``lm_dsa_step_check.py``
where it says ``lm_step_check``: its seven numbers and four more) — as
``lm_gqa_train_steps.py`` and ``lm_gdn_train_steps.py`` do for their
families. Two things this family adds to the rig: before each of the
first steps it asks the meta-arch for the step's SELECTION of keys beside
its expert choices (``selection``: one forward pass gives both; packed
bits, 33.5 MB a layer at 16,384 tokens), which the reference follows as
it follows the choices; and the program's side of the check carries each
first step's index loss and the run's ring rows (``dsa_select_excess``).

One sequence counts as one image: ``train_img_per_s_chip`` x 16,384 =
tokens/s/chip.

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import time

import numpy as np

import lm_dsa_step_check
import lm_dsa_weights
import weights
from reference import keye_vl2_fp32
from run import DRIVER_DIR, load_module

_base = load_module(DRIVER_DIR, "lm_train_steps")  # this module's own copy
_base.kimi_linear_fp32 = keye_vl2_fp32
_base.lm_weights = lm_dsa_weights
_base.lm_step_check = lm_dsa_step_check

train_steps = _base.train_steps


class Rig(_base.Rig):
    def first_steps(self) -> dict:
        """``lm_train_steps.Rig.first_steps`` with ``selection`` where it
        asks ``routing`` (each entry of ``choices`` is then the pair the
        reference's ``first_steps`` takes: expert choices, packed
        selection), and each first step's index loss and the run's ring
        rows beside what it returns."""
        import jax

        n = int(self.mix["warmup_steps"])
        t0 = time.perf_counter()
        self.choices = []
        with self.setup.mesh:
            selection = jax.jit(self.setup.meta.selection)
        for i in range(n):
            with self.setup.mesh:
                self.choices.append(tuple(np.asarray(a) for a in selection(
                    self.state.params["student"], self.pending)))
            self.loop(stop_at_step=i + 1)
            if i == 0:
                self.first_step_s = time.perf_counter() - t0
                self.first_moment = jax.device_get(
                    self.state.opt_state.adam.mu["backbone"])
        self.flush()  # warms the flush path too; the window starts with an empty ring
        abstract = self.setup.state.params["student"]

        def changes(student, key):
            old = lm_dsa_weights.fill_leaves(abstract, key)
            return train_steps._leaf_norms(
                jax.tree.map(lambda a, b: a - b, student, old))

        change = jax.jit(changes)(
            self.state.params["student"],
            weights.seed_key(self.seed, weights.FILL_STREAM))
        jax.block_until_ready((self.state.step, self.pending))
        return {
            "losses": [row["total_loss"] for row in self.rows[:n]],
            "index_losses": [row["lm_index_loss"] for row in self.rows[:n]],
            "change_norms": lm_dsa_weights.reference_tree(
                jax.tree.map(np.float64, change)["backbone"]),
            "rows": self.rows,
        }

    def reference(self, variant: str = "fp32", against=None,
                  keep_host: bool = False) -> dict:
        """``lm_train_steps.Rig.reference``, each step's (choices,
        selection) handed on as the pair it is."""
        import jax
        import jax.numpy as jnp

        n = int(self.mix["warmup_steps"])
        w = lm_dsa_weights.reference_tree(lm_dsa_weights.fill(
            self.setup.state.params["student"], self.seed)["backbone"])
        batches = [jnp.asarray(self.pool[i % len(self.pool)]["tokens"])
                   for i in range(n)]
        scale = np.float32(1.0)
        if against is None:
            against = lm_dsa_weights.reference_tree(self.first_moment)
            scale = np.float32(1.0 / (1.0 - self.recipe.beta1))
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a * scale - b))))
        out = {}

        def keep(g):  # leaf by leaf: one leaf of the other side's at a time
            out["grad_diff_norms"] = jax.tree.map(
                lambda a, b: np.float64(diff(a, b)), against, g)
            out["other_grad_norms"] = jax.tree.map(
                lambda a: np.float64(np.linalg.norm(a)) * scale, against)
            if keep_host:
                out["gradient_host"] = jax.device_get(g)

        out.update(keye_vl2_fp32.first_steps(
            w, batches, [tuple(jnp.asarray(a) for a in c) for c in self.choices],
            self.shape, self.recipe, self.start_it, variant, keep_gradient=keep))
        return out


_base.Rig = Rig
run = _base.run
