"""Architecture modules: everything the benchmark knows of one architecture.

A configuration file `configs/<name>.json` names its module with the
top-level key `"arch"` (beside `"model"`): `arch/<arch>.py`. The harness
resolves it once per run with `harness.arch_module(cfg)`, before anything
compiles, and takes the architecture from it alone. A new architecture is
a new module here and a configuration that names it; no other file of the
benchmark changes.

A module defines these functions. `model` is the configuration's
`"model"` object, `train` its `"train"` object.

  check_config(model)
      Raise `harness.HarnessError`, naming the key, for any key or value
      of `model` that the module does not compute.
  make_params(seed, model, dtype="float32")
      The whole parameter tree, in the layout the program keeps, made on
      the default device from the seed in one jitted call, in `dtype`.
  change_norms(params, seed, model, dtype="float32")
      Per-leaf L2 norm of `params` less the seed's starting parameters.
  train_steps(seed, model, train, batches)
      The plain float32 reference over `batches` from the seed's weights:
      {"loss": [...], "grad_norm": [...], "grad1": {leaf: norm},
      "change": {leaf: norm}, "sites": [{(site, kind, layer): stats}]},
      with every triple of `layer_sites(model)` and the scalar sites
      (`reference.SCALAR_SITES`) in each step's `sites`.
  layer_sites(model)
      The (site, kind, layer) triples of the tensor probe sites that the
      reference states, in the order of its partials. Sites may differ by
      layer.
  flops_per_token(model, seq_len)
      Model FLOPs a trained token needs, forward and backward,
      recomputation not counted.
  site_bytes(model, batch, seq_len)
      {(site, kind): bytes} of the tensors at each probe site in one
      step, summed over the layers where the site fires.

The shared pieces stay outside: seeds and batches (`weights.py`), the
row-by-row train loop, AdamW, site statistics and probe map effects
(`reference.py`), and the probed-bytes sum (`flops.py`).
"""
