"""Train-step builder: the compute loop the reference left to user frameworks.

Functional and jit-first: one ``TrainState`` pytree, one compiled
``train_step`` (value_and_grad → optax update), gradient accumulation as a
``lax.scan`` over microbatches (stays on-device, no host sync), donation of
the input state so params/optimizer memory is reused in place.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import optax

from tony_tpu.parallel.sharding import ShardingRules


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params: Any, optimizer: optax.GradientTransformation) -> "TrainState":
        return cls(params=params, opt_state=optimizer.init(params), step=jnp.zeros((), jnp.int32))


@dataclass(frozen=True)
class OptimizerConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    # dtype of Adam's first moment; "" keeps optax's default (the PARAM
    # dtype — so bf16-param models already hold bf16 moments). Set
    # "bfloat16" to halve mu's HBM when params are f32, or "float32" to
    # upcast it for extra stability on bf16-param models.
    mu_dtype: str = ""

    def build(self) -> optax.GradientTransformation:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.learning_rate, self.warmup_steps, max(self.total_steps, self.warmup_steps + 1)
        )
        return optax.chain(
            optax.clip_by_global_norm(self.grad_clip),
            optax.adamw(
                schedule, b1=self.b1, b2=self.b2, weight_decay=self.weight_decay,
                mu_dtype=jnp.dtype(self.mu_dtype) if self.mu_dtype else None,
            ),
        )


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jax.Array, dict]],
    optimizer: optax.GradientTransformation,
    accum_steps: int = 1,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """loss_fn(params, batch) -> (loss, aux). Returns a jitted step with the
    state donated (in-place param/optimizer update on device).

    With accum_steps > 1, the batch's leading dim must be
    ``accum_steps * microbatch`` and gradients average over a lax.scan.
    """

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return loss, aux, grads

        def micro(carry, mb):
            loss_acc, grads_acc = carry
            (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            return (loss_acc + loss, jax.tree.map(jnp.add, grads_acc, grads)), None

        microbatches = jax.tree.map(
            lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]), batch
        )
        zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
        (loss_sum, grads_sum), _ = jax.lax.scan(micro, (jnp.zeros((), jnp.float32), zeros), microbatches)
        inv = 1.0 / accum_steps
        return loss_sum * inv, {}, jax.tree.map(lambda g: g * inv, grads_sum)

    def train_step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        loss, aux, grads = compute_grads(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            "step": state.step + 1,
            **{k: v for k, v in aux.items() if k != "loss"},
        }
        return TrainState(params, opt_state, state.step + 1), metrics

    return jax.jit(train_step, donate_argnums=0)


def make_pp_train_step(
    value_and_grad_fn: Callable[[Any, Any], tuple[jax.Array, dict, Any]],
    optimizer: optax.GradientTransformation,
) -> Callable[[TrainState, Any], tuple[TrainState, dict]]:
    """Train step from a function that produces gradients itself —
    ``value_and_grad_fn(params, batch) -> (loss, aux, grads)``. The 1F1B
    pipeline schedule (llama.pp_value_and_grad) hand-runs its backward
    inside the pipeline loop, so it cannot go through jax.value_and_grad;
    everything after gradients (optimizer, metrics, donation) is identical
    to make_train_step."""

    def train_step(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        loss, aux, grads = value_and_grad_fn(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
            "step": state.step + 1,
            **{k: v for k, v in aux.items() if k != "loss"},
        }
        return TrainState(params, opt_state, state.step + 1), metrics

    return jax.jit(train_step, donate_argnums=0)


#: What the remat chooser leaves free of a device's ``bytes_limit``: the
#: compiled step's report counts one program, not the batches the input
#: pipeline holds ahead, the allocator's fragmentation or a checkpoint's
#: copies. A constant reckoned on the chip (PERF.md section 6, PR 47), not a
#: setting.
REMAT_MARGIN = 0.06


#: What the link between a v5e chip and its host's pinned memory carries each
#: way while both ways run (10.3-10.9 GB/s in one program, four chips at once
#: 94-99% of one alone; 13.9 in and about 14.6 out one way at a time: PERF.md
#: section 6, PR 56), and the share of a step's reckoned time that the
#: host-kept bytes may take each way: a layer's forward is a third of its
#: step, and a value must leave behind the rest of the forward of the layer
#: that formed it. Constants reckoned on the chip, not settings.
HOST_LINK_BYTES_PER_S = 10e9
HOST_LINK_SHARE = 0.25


def remat_candidates(
    ladder: Sequence[tuple[str, ...]], named: Mapping[str, int], layers: int,
    host_names: Sequence[str], step_seconds: float | None,
) -> list[tuple[tuple[str, ...], tuple[str, ...], int]]:
    """The remat ladder with its host parts: ``(saved, host, bytes)`` rising in
    the bytes a device and step holds for them, ``choose_remat_rung``'s
    ``saved`` and what ``ops/attention.Rung`` takes.

    ``ladder`` are the cumulative rungs of names, ``named[name]`` a name's
    bytes a device and step over all ``layers``. Every rung stands as it is,
    and again with the ``host_names`` kept on the host instead of saved or
    replayed, whether or not the rung holds them (the host part is not bound
    to the ladder's order): a host-kept name costs the device one layer's
    bytes, the copy on its way back. No host part where ``step_seconds`` (the
    step's flops a device over the peak) is None, or where the names' bytes
    over ``HOST_LINK_BYTES_PER_S`` take more than ``HOST_LINK_SHARE`` of it.
    A rung that saves nothing the one below does not is left out (a family's
    block holds the names it holds). A rung with the names on the device stands
    above the one that keeps the same on the host: it costs the link nothing."""
    host = tuple(n for n in host_names if named.get(n))
    moved = sum(named[n] for n in host)
    if step_seconds is None or moved / HOST_LINK_BYTES_PER_S > HOST_LINK_SHARE * step_seconds:
        host = ()
    found: dict[tuple, int] = {}
    for rung in ladder:
        found.setdefault((rung, ()), sum(named.get(n, 0) for n in rung))
        if host:
            saved = tuple(n for n in rung if n not in host)
            found.setdefault((saved, host), sum(named.get(n, 0) for n in saved) + moved // max(layers, 1))
    out: list[tuple[tuple[str, ...], tuple[str, ...], int]] = []
    kept: list[tuple[set[str], tuple[str, ...]]] = []
    for (saved, on_host), held in sorted(found.items(), key=lambda kv: (kv[1], len(kv[0][1]))):
        names = {n for n in saved + on_host if named.get(n)}
        if not any(names <= k and on_host == h for k, h in kept):
            out.append((saved, on_host, held))
            kept.append((names, on_host))
    return out


def choose_remat_rung(
    saved: Sequence[int], limit: int | None, held: int,
    compile_rung: Callable[[int], tuple[Any, int | None]],
) -> tuple[int, Any, str]:
    """Which rung of a remat ladder a train step runs: ``(rung, executable, why)``.

    ``saved[i]`` is what rung i keeps of the forward for the backward, in
    bytes a device and step, rising with i (``saved[0] == 0``: everything is
    recomputed); ``limit`` the device's ``bytes_limit``; ``held`` a first
    reckoning of what the step holds at rung 0 (state, gradients, batch, one
    block's working set); ``compile_rung(i)`` compiles the step at rung i and
    returns it with its ``memory_analysis()`` peak (None where the backend
    gives none), raising the compiler's out-of-memory error where the step
    does not fit at all.

    The first rung tried is the highest the reckoning puts under the limit
    less the margin. From then on the compiler's own report decides: it says
    what rung 0 holds (the report less that rung's saved bytes), so the step
    goes down while it is over or the compile ran out of memory, and up where
    the report leaves room for a higher rung's bytes. Shapes, the device's
    limit and the compile's report are equal in every process of a gang, so
    every process reaches the same rung; that is why no host-side state (the
    free memory at this moment, which differs between them) is read. No limit
    (the CPU): rung 0 and no executable, the caller's jitted step runs as ever.
    """
    top = len(saved) - 1
    if limit is None:
        return 0, None, "the device reports no bytes_limit"
    budget = int(limit * (1 - REMAT_MARGIN))

    def highest(base: int) -> int:
        return max((i for i in range(top + 1) if base + saved[i] <= budget), default=0)

    done: dict[int, tuple[Any, int | None]] = {}
    over: dict[int, str] = {}  # rung -> why it does not fit
    rung = highest(held)
    while True:
        if rung not in done:
            try:
                done[rung] = compile_rung(rung)
            except jax.errors.JaxRuntimeError as e:
                if rung == 0 or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                over[rung] = f"rung {rung}'s compile ran out of memory"
                rung -= 1
                continue
        executable, used = done[rung]
        if used is None:
            return rung, executable, "the compiled step reports no memory"
        base = used - saved[rung]
        if used > budget and rung > 0:
            over[rung] = f"rung {rung} compiled to {used / 1e9:.2f} GB of {budget / 1e9:.2f}"
            rung = min(rung - 1, highest(base))
            continue
        higher = highest(base)
        while higher in over:
            higher -= 1
        if higher <= rung:
            why = "the top rung fits" if rung == top else over.get(rung + 1) or (
                f"rung {rung + 1} would hold {(base + saved[rung + 1]) / 1e9:.2f} GB of {budget / 1e9:.2f}")
            return rung, executable, why
        rung = higher


def sharded_init(
    init_fn: Callable[[], Any],
    rules: ShardingRules,
    mesh,
    optimizer: optax.GradientTransformation,
) -> TrainState:
    """Initialize params directly onto the mesh (jit with out_shardings so
    large models never materialize unsharded on one device), then build the
    optimizer state under the same sharding."""
    from jax.sharding import NamedSharding, PartitionSpec

    abstract = jax.eval_shape(init_fn)
    out_sharding = rules.sharding_tree(abstract, mesh)
    params = jax.jit(init_fn, out_shardings=out_sharding)()
    # zeros_like under optax.init inherits each param's sharding, so the
    # optimizer state (the FSDP memory win) lands sharded too.
    opt_state = optimizer.init(params)
    # scalar leaves (optax step counts, TrainState.step) get a DEFAULT
    # single-device placement — harmless uncommitted at init, but a restored
    # checkpoint COMMITS every leaf to its recorded sharding, and a scalar
    # pinned to device 0 next to mesh-sharded params is an incompatible-
    # devices error in the first jitted step after resume. Replicate them
    # over the mesh so the whole TrainState (and any checkpoint of it)
    # lives on the mesh — which also makes checkpoints restore cleanly onto
    # a DIFFERENT mesh shape (elastic re-pack).
    repl = NamedSharding(mesh, PartitionSpec())

    def _on_mesh(x):
        if isinstance(x, jax.Array) and not isinstance(x.sharding, NamedSharding):
            return jax.device_put(x, repl)
        return x

    opt_state = jax.tree.map(_on_mesh, opt_state)
    return TrainState(
        params=params, opt_state=opt_state,
        step=jax.device_put(jnp.zeros((), jnp.int32), repl),
    )


class Throughput:
    """Wall-clock tokens/s + MFU meter around the jitted step (host side).
    ``peak_flops=None`` (a CPU run) reports no MFU."""

    def __init__(self, tokens_per_step: int, flops_per_token: int, n_chips: int,
                 peak_flops: float | None):
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.n_chips = max(n_chips, 1)
        self.peak_flops = peak_flops
        self._t0: float | None = None
        self.steps = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self.steps = 0

    def step(self) -> None:
        self.steps += 1

    def report(self) -> dict:
        dt = time.perf_counter() - (self._t0 or time.perf_counter())
        if dt <= 0 or self.steps == 0:
            tps = step_ms = 0.0
        else:
            tps = self.tokens_per_step * self.steps / dt
            step_ms = 1000 * dt / self.steps
        out = {
            "tokens_per_sec": tps,
            "tokens_per_sec_per_chip": tps / self.n_chips,
            "step_time_ms": step_ms,
        }
        if self.peak_flops is not None:
            out["mfu"] = tps * self.flops_per_token / (self.peak_flops * self.n_chips)
        return out
