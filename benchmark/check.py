"""The comparison that decides `correct`: a child of its own that takes the
chip once the job has been stopped (`python check.py <input.json>`).

Training: the program's forward (its kernels, bf16) against the float32
reference on weights and tokens made from the seed, and, where the cell's
limits name `grad_rel_rms`, the gradient of the program's loss function (its
backward kernels, its chunked cross-entropy, its remat) with respect to the
leaves the reference names (`GRAD_LEAVES`) against the reference's. Serving:
the reference teacher-forced over what the engine returned, and how far below
the reference's best logit the engine's choices lie. `control=True` also
computes the control (the reference in its `CONTROL` precision, in the
program's place): used when a limit is set and by the test, never by the
benchmark's own runs. Program and reference are the family's, found by the
configuration's `module` (families/__init__.py).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import families
from spec import CHECK_TAIL as TAIL


def _pad_to(tokens: np.ndarray, block: int) -> np.ndarray:
    return np.concatenate([tokens, np.zeros((-len(tokens)) % block, tokens.dtype)])


def _rel_rms_device(a, ref) -> jax.Array:
    a, ref = a.astype(jnp.float32), ref.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((a - ref) ** 2) / jnp.sum(ref ** 2))


def _leaf(tree, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def _with_leaf(tree, path: tuple, leaf):
    return leaf if not path else {**tree, path[0]: _with_leaf(tree[path[0]], path[1:], leaf)}


def _with_leaves(params: dict, leaves: dict) -> dict:
    """`leaves`: "a/b" -> array, put at the path a, b of the parameter tree."""
    for name, leaf in leaves.items():
        params = _with_leaf(params, tuple(name.split("/")), leaf)
    return params


class TrainComparison:
    """The training comparison's jitted programs, made once (eager operations
    would each compile, and none of those compiles is kept), then `run` for a
    seed: the harness's child runs one, `limits.py` a dozen in one process."""

    def __init__(self, module, cfg, mesh, sizes: dict, seq: int):
        self.sizes, self.seq, qb = sizes, seq, min(512, seq)
        R = self.reference = families.reference(sizes)
        grad_leaves = {"/".join(path): path for path in R.GRAD_LEAVES}

        @jax.jit
        def program(params, batch):
            out = module.forward(params, batch[:, :-1], cfg, mesh)
            logits = out[0] if isinstance(out, tuple) else out
            _, metrics = module.loss_fn(params, {"tokens": batch}, cfg, mesh)
            return logits[0, -TAIL:].astype(jnp.float32), metrics.get("ce_loss", metrics["loss"])

        @functools.partial(jax.jit, static_argnames=("prec",))
        def plain(params, row, prec):
            full = R.forward(params, row[:-1], sizes, prec, qb)
            return full[-TAIL:], R.nll(full, row[1:]).mean()

        # d(mean loss of the step's batch)/d(the reference's GRAD_LEAVES): the
        # program's own loss function through jax.grad, as its step takes it
        @jax.jit
        def program_grad(params, batch):
            leaves = {k: _leaf(params, path) for k, path in grad_leaves.items()}
            return jax.grad(lambda lv: module.loss_fn(_with_leaves(params, lv), {"tokens": batch}, cfg, mesh)[0])(leaves)

        @functools.partial(jax.jit, static_argnames=("prec",))
        def plain_grad_row(params, row, prec):
            leaves = {k: _leaf(params, path).astype(jnp.float32) for k, path in grad_leaves.items()}
            return jax.grad(lambda lv: R.nll(R.forward(_with_leaves(params, lv), row[:-1], sizes, prec, qb),
                                             row[1:]).mean())(leaves)

        @jax.jit
        def grad_errors(got, ref):
            return {k: _rel_rms_device(got[k], ref[k]) for k in grad_leaves}

        self.program, self.plain, self.program_grad = program, plain, program_grad
        self.plain_grad_row, self.grad_errors = plain_grad_row, grad_errors

    def plain_grad(self, params, batch, prec: str) -> dict:
        # a row at a time and the mean on the device: under one jit the compiler
        # unrolls the rows and holds every row's temporaries at once (compile-only,
        # PR 24: 14.0 GB for two rows of 8192 at 4 layers, 6.6 GB for one)
        total = None
        for i in range(batch.shape[0]):
            g = self.plain_grad_row(params, batch[i], prec)
            total = g if total is None else jax.tree.map(jnp.add, total, g)
        return jax.tree.map(lambda a: a / batch.shape[0], total)

    def run(self, params, seed: int, rows: int, control: bool = False, grad: bool = False) -> dict:
        """`params` are the seed's weights as the program holds them (sharded
        over the mesh if there is one); `rows` sequences (the step's batch) go
        through the program. The reference runs on one chip: the first row for
        the logits (16 layers x 8192 positions in float32 take seconds a row,
        and one row's logits decide), every row for the gradient."""
        R = self.reference
        tokens = compare.zipf_tokens(seed + 1, rows * (self.seq + 1), self.sizes["vocab"]).reshape(rows, self.seq + 1)
        batch = jnp.asarray(tokens)
        t0 = time.time()
        tail, prog_loss = jax.block_until_ready(self.program(params, batch))
        t_program = time.time() - t0
        one = jax.devices()[0]
        ref_params, ref_batch = jax.device_put(params, one), jax.device_put(batch, one)
        ref_tail, ref_loss = self.plain(ref_params, ref_batch[0], "f32")
        ref_tail, ref_loss = np.asarray(ref_tail), float(ref_loss)
        result = {
            "logit_rel_rms": compare.rel_rms(tail, ref_tail),
            "loss_gap": abs(float(prog_loss) - ref_loss),
            "program_loss": float(prog_loss), "reference_loss": ref_loss,
            "program_s": round(t_program, 2), "reference_s": round(time.time() - t0 - t_program, 2),
        }
        if control:
            ctl_tail, ctl_loss = self.plain(ref_params, ref_batch[0], R.CONTROL)
            result["control_logit_rel_rms"] = compare.rel_rms(ctl_tail, ref_tail)
            result["control_loss_gap"] = abs(float(ctl_loss) - ref_loss)
        if not grad:
            return result
        t0 = time.time()
        got = jax.device_put(jax.block_until_ready(self.program_grad(params, batch)), one)
        t_program = time.time() - t0
        ref = jax.block_until_ready(self.plain_grad(ref_params, ref_batch, "f32"))
        by_leaf = {k: float(v) for k, v in self.grad_errors(got, ref).items()}
        # the number compared: the largest relative RMS error of the leaves
        result.update(grad_rel_rms=max(by_leaf.values()), grad_rel_rms_by_leaf=by_leaf,
                      grad_program_s=round(t_program, 2), grad_reference_s=round(time.time() - t0 - t_program, 2))
        if control:
            ctl_grad = self.plain_grad(ref_params, ref_batch, R.CONTROL)
            ctl = {k: float(v) for k, v in self.grad_errors(ctl_grad, ref).items()}
            result.update(control_grad_rel_rms=max(ctl.values()), control_grad_rel_rms_by_leaf=ctl)
        return result


@functools.partial(jax.jit, static_argnames=("sizes_key", "prec"))
def _teacher_forced(params, seq, start, chosen, sizes_key, prec):
    """Reference rows for the answer's positions: (best logit - chosen token's
    logit, whether the chosen token is the argmax, the argmax), each [len(chosen)]."""
    sizes = dict(sizes_key)
    logits = families.reference(sizes).forward(params, seq, sizes, prec)
    rows = jax.lax.dynamic_slice_in_dim(logits, start, chosen.shape[0])
    return compare.chosen_gap(rows, chosen), rows.argmax(-1) == chosen, rows.argmax(-1), rows


def check_serve(params, sizes: dict, samples: list[dict], control: bool = False,
                pad_seq: int = 1024, pad_answer: int = 512) -> dict:
    """`samples`: finished requests, each {"prompt": [...], "tokens": [...]}.
    The reference reads prompt + answer; the row at the last prompt position
    predicts the first generated token, and so on. Sequences are padded at the
    end to a few fixed lengths so that few programs compile."""
    key, control_prec = tuple(sorted(sizes.items())), families.reference(sizes).CONTROL
    gaps, agree, n, ctl_gaps = [], 0, 0, []
    for sm in samples:
        prompt, toks = sm["prompt"], sm["tokens"][:pad_answer]
        # room for the padded answer window after the last prompt position
        seq = _pad_to(np.asarray(prompt + toks[:-1] + [0] * pad_answer, np.int32), pad_seq)
        chosen = np.zeros(pad_answer, np.int32)
        chosen[:len(toks)] = toks
        gap, same, _, rows = _teacher_forced(params, jnp.asarray(seq), len(prompt) - 1, jnp.asarray(chosen), key, "f32")
        gaps.append(float(np.asarray(gap)[:len(toks)].max()))
        agree += int(np.asarray(same)[:len(toks)].sum())
        n += len(toks)
        if control:
            _, _, ctl_choice, _ = _teacher_forced(params, jnp.asarray(seq), len(prompt) - 1, jnp.asarray(chosen), key,
                                                  control_prec)
            ctl_gaps.append(float(np.asarray(compare.chosen_gap(rows, ctl_choice))[:len(toks)].max()))
    result = {"worst_gap": max(gaps), "argmax_agree": agree, "tokens": n}
    if control:
        result["control_worst_gap"] = max(ctl_gaps)
    return result


def train_setup(config: str, deployment: str, loop_argv: list[str]):
    """(comparison, a function seed -> the seed's weights, rows): the model
    and the mesh as the loop builds them for the same flags. The step's own
    batch shape: the kernels then compile as they do in the loop."""
    import spec
    from chipside import sharded_weights
    from tony_tpu.parallel import MeshSpec
    from tony_tpu.train.loop import parse_loop_args

    sizes = spec.model_sizes(spec.config(config), deployment)
    loop, _ = parse_loop_args(loop_argv)
    module, cfg = families.load(sizes["module"]).program(sizes, loop.seq_len)
    mesh = MeshSpec.auto(model=loop.model_axis, context=loop.context_axis,
                         expert=loop.expert_axis, stage=loop.stage_axis).build()
    return (TrainComparison(module, cfg, mesh, sizes, loop.seq_len),
            lambda seed: sharded_weights(module, cfg, mesh, sizes, seed), loop.batch_size)


def main(argv: list[str]) -> int:
    """`python check.py <input.json>`; the result is the last line of stdout."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import spec
    from chipside import MemoryPeak, seed_weights
    from tony_tpu.runtime import enable_compile_cache

    with open(argv[1]) as f:
        job = json.load(f)
    enable_compile_cache()
    if job.get("kind") == "train":
        comparison, weights, rows = train_setup(job["config"], job["deployment"], job["loop_argv"])
        result = comparison.run(weights(job["seed"]), job["seed"], rows, control=bool(job.get("control")),
                                grad=bool(job.get("grad")))
    else:
        sizes = spec.model_sizes(spec.config(job["config"]), job["deployment"])
        params = seed_weights(sizes, job["seed"])
        result = check_serve(params, sizes, job["samples"], control=bool(job.get("control")))
    # how near this child came to the chip's memory (shown by the harness, not compared)
    print(json.dumps({**result, "child_memory_peak_bytes": MemoryPeak().sample()}), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
