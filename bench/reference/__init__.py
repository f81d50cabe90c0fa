"""Plain references, one module per architecture.

A configuration file names its module under ``"reference"``
(``bench/configs/<config>.json`` -> ``bench/reference/<name>.py``), and
the harness takes everything that depends on the architecture from it.
A module defines:

- ``FAMILIES``: {registry family: activation} of the program's model
  registry entries (``repro.configs``) that it implements;
- ``Shape``, with ``Shape.from_config(run)``, ``run`` being the
  configuration file's sizes;
- ``init_params(shape, key_data, dtype=jnp.float32)``: the seeded weights,
  which both the program and the reference start from;
- ``Reference(shape, AdamW, S, devices, dtype, precision)``: a subclass of
  ``common.Reference``, whose ``run(seed, steps)`` trains the first steps;
- ``step_flops(run, lengths)``: model FLOPs to train once on samples of
  these lengths, which ``mfu`` reads;
- ``AdamW``, ``seed_key_data``, ``leaf_sq_norms``, ``change_sq_norms`` and
  ``sq_to_norms``: as a rule imported from ``common``.
"""
