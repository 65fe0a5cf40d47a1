"""A model family, found by the `module` key of a configuration's file.

`configs/<config>.json` names its family (`"module": "<name>"`), and
`families/<name>.py` holds everything the harness knows about that family, so
that a later PR adds a family as files and edits nothing that is here:

  sizes(cfg, deployment) -> dict      the configuration's published keys as the plain numbers
                                      that this family's program, reference and counts work
                                      from ("module" and "vocab" among them: the harness reads
                                      those two). The family says which keys it reads; one it
                                      does not know is an error (`known_keys_only`). No JAX.
  program(sizes, max_seq)             -> (the program's model module, its config object): what
                                      `run_lm_training(module, cfg, ...)` takes. Imports the
                                      program, so only a process that holds the chip calls it.
  serve_install(sizes, bench)         in the replica, before `serving_http.main()`: put the
                                      configuration where the program looks for it and hand the
                                      engine the seed's weights. `bench` is the spec the harness
                                      wrote: config, deployment, seed, out_dir and the workload's
                                      whole `engine` block. A family that cannot be served yet
                                      raises SystemExit and says why.
  REFERENCE                           the module (under benchmark/) of its plain reference:
                                      seed_key(seed), init_weights(key, sizes), forward(params,
                                      tokens, sizes, prec, q_block) -> logits, nll(logits,
                                      targets), CONTROL (the `prec` of the control, "f32" being
                                      the reference itself) and GRAD_LEAVES (paths into the
                                      parameter tree whose gradient the training comparison reads)
  COUNTS                              the module of its counts: train_flops_per_token(sizes, seq)
                                      and, for each kernel a metric names, <kernel>_operands(sizes,
                                      seq) (a pattern over the HLO line that tells the kernel's
                                      calls apart) and <kernel>_layer_step(sizes, rows, seq)
                                      (the (operations, bytes) of each call a layer makes a step)

What is the same for every family stays shared: compare.py (rel_rms,
chosen_gap, zipf_tokens), counts.py (the band's pairs, the roofline, the peaks).

No JAX here or at the top of a family's file: run.py imports both.
"""

from __future__ import annotations

import importlib
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


class NoFamily(LookupError):
    """The configuration's `module` names no file under families/."""


def load(name: str):
    path = os.path.join(HERE, name + ".py")
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name) or not os.path.isfile(path):
        raise NoFamily(f"the configuration's module is {name!r} and there is no family file {path}: add it "
                       f"(what it has to answer for is in {os.path.join(HERE, '__init__.py')})")
    return importlib.import_module("families." + name)


def reference(sizes: dict):
    """The family's plain reference (imports JAX)."""
    return importlib.import_module(load(sizes["module"]).REFERENCE)


def counts(sizes: dict):
    return importlib.import_module(load(sizes["module"]).COUNTS)


def depth(cfg: dict, deployment: str) -> int:
    """Depth is the one size cut per deployment: a number, or one a deployment."""
    n = cfg["num_hidden_layers"]
    if isinstance(n, dict):
        if deployment not in n:
            raise KeyError(f"configuration has no depth for deployment {deployment!r}: {sorted(n)}")
        n = n[deployment]
    return int(n)


#: keys of a configuration's file that say nothing of the model's arithmetic
NOTES = ("source", "module", "reduced", "assumed", "deployments")


def known_keys_only(cfg: dict, known, family: str) -> None:
    unknown = sorted(set(cfg) - set(known) - set(NOTES))
    if unknown:
        raise KeyError(f"family {family!r} does not know the configuration's {unknown}: a key that reaches neither "
                       "the program nor the reference is a size silently dropped")
