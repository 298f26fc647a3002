"""The only place the benchmark reaches into the program.

The zoo's entries depart from the published files (``mistral_7b`` has a
4,096 window and a 32,000 vocabulary, ``mixtral_8x7b`` a 5e5
``rope_theta`` and 8k positions), and neither `plx serve` nor the job
spec can override every field. So the configuration's file is turned
into its family's config dataclass and registered in the family's
``CONFIGS`` (and the zoo's factory table) under the configuration's
name; from there on the program's normal path runs: ``ServingServer``
for serving, ``run_jaxjob`` for training.

This file names no family. A configuration's ``family`` key names a
file, ``families/<family>.py`` (``spec.load_family``: beside the
configuration's own tree first, then under the benchmark's), and a PR
that brings a model brings that file with its configuration and its
reference, and edits nothing here.

**What the harness asks**, of the three things such a PR brings.

*The family file* gives ``build(config, role) -> (module, cfg)``: the
program's family module and an instance of its config dataclass for
`role` (``serve`` or ``train``), read from the configuration file's own
keys, whatever the published file calls them, at the depth and context
limit of the configuration's ``serve`` / ``train`` section. Every check
that belongs to the family (what its program cannot express) is made
there. It may give ``forward_flops_per_token(config, layers, seq_len)``,
the benchmark's own count where the layers are not all alike
(``harness/flops.py``). It imports the program inside ``build`` only:
the parent process loads it and never imports jax.

*Of the pair, and of the program behind it*, the harness uses:

- ``module.CONFIGS``, a dict: `register` writes ``cfg`` into it under
  the configuration's name, and ``models._FACTORIES[name]`` to a call
  of ``module.model_def(name, **overrides)``. The program finds a name
  by walking fixed lists of family modules (``serving/server.py
  _family``, ``runtime/loop.py _model_config_cls`` and ``_get_cfg``), so
  a new ``models/<family>.py`` has to be in those lists;
- ``cfg.vocab_size`` (the load generator draws token ids under it) and
  ``cfg.n_layers`` (reported; the reference is given it as its depth);
- serving: ``ServingServer(name, seed=, batching="continuous",
  kv="paged", slots=, page_size=, kv_pages=, prefix_cache=True)`` from
  the configuration's ``serve`` section, its ``/v1/generate``,
  ``/requests/<id>/timeline`` and ``engine.stats()``
  (``kv_invariant_violations``, ``step_failures``, ``rejected``,
  ``decode_steps`` decide `correct` or feed readers). The engine asks
  the module for ``decode_step_ragged``, ``cb_init_cache``,
  ``cb_prefill``, ``cb_admission``, ``cb_validate``,
  ``insert_cache_row``, ``decode_step_paged``, ``paged_init_cache``,
  ``paged_prefill_kv``, ``paged_insert_prefill`` and, for the radix
  cache, ``paged_gather``, ``paged_prefill_suffix_kv``,
  ``paged_insert_suffix``; weights come from
  ``module.init(cfg, jax.random.key(seed))["params"]``;
- training: ``run_jaxjob`` of a ``jaxjob`` whose ``runtime:`` section is
  `runtime_section` (plus ``capacity_factor`` where the ``train``
  section has one) on the ``train`` section's ``mesh``, with
  ``on_metrics`` after every step (``loss``, ``grad_norm``),
  ``should_stop``, and a state of ``params`` and an ``opt_state`` whose
  adam part has ``mu`` (``train_phase.StateWatch`` reads both).

*The reference module* (the configuration's ``reference`` key, a file
under ``reference/`` that imports nothing of the program and makes the
weights again from the seed, bit for bit the program's):

- ``init_weights(config, layers, seed)``;
- ``logits(config, weights, tokens, precision)``, tokens ``[1, n]`` at
  one padded length, ``precision`` ``"highest"`` or, for the control,
  ``"int8"``;
- for a training cell ``train_steps(config, layers, seed, *, steps,
  batch, seq_len, lr, wd, clip, precision, capacity_factor,
  shardings)`` returning ``steps`` (a ``loss`` and a ``grad_norm``
  each), ``grad0_leaf`` and ``update_leaf``: norms by the leaf, named
  as the program's ``params`` tree names its leaves (``/``-joined keys).

``tools/aot_memory.py`` reaches further into the two families that are
here; it is a sizing tool, not on a run's path.
"""

from __future__ import annotations

from harness import spec


def build_model_config(config: dict, role: str):
    """(family module, its config dataclass instance) for `role`
    (``serve`` or ``train``), as the configuration's family file reads
    it from the configuration's own keys."""
    return spec.load_family(config).build(config, role)


def register(config: dict, role: str):
    """Register the configuration in the program's zoo; returns
    (model name, family module, model config)."""
    from polyaxon_tpu import models

    family, cfg = build_model_config(config, role)
    name = config["name"]
    family.CONFIGS[name] = cfg
    models._FACTORIES[name] = (
        lambda **overrides: family.model_def(name, **overrides))
    return name, family, cfg


def runtime_section(config: dict, model: str, seed: int, seq_len: int,
                    steps: int = 1_000_000) -> dict:
    """The ``runtime:`` section of the JAXJob this cell submits."""
    train = config["train"]
    return {
        "model": model, "dataset": train["dataset"], "steps": steps,
        "optimizer": train["optimizer"],
        "learning_rate": train["learning_rate"],
        "weight_decay": train["weight_decay"],
        "grad_clip_norm": train["grad_clip_norm"],
        "seq_len": seq_len,
        "global_batch_size": train["global_batch_size"],
        "log_every": train["log_every"], "prefetch": train["prefetch"],
        "remat": train["remat"], "attention_impl": train["attention_impl"],
        "seed": seed,
    }
