"""Model families: one module per Hugging Face ``model_type``, found by
``spec.family`` as ``bench/families/<model_type>.py``. A family is
everything of the benchmark that knows an architecture:

- ``dims(config) -> dm``: the sizes, a frozen, hashable dataclass with
  at least ``name``, ``model_type``, ``layers``, ``d``, ``vocab`` and
  ``norm_eps`` (the reference jits with ``dm`` static);
- ``leaf_shapes(dm)``: ``{path: (shape, kind, scale)}`` of the weight
  tree (``weights.make_weights``), ``embed``, ``final_norm`` and
  ``unembed`` among them at the top;
- ``program_config(dm)``: the serving program's ``ModelConfig``;
- ``hidden(params, dm, tokens, mode)``: the float32 reference up to the
  final norm, ``mode="fp8"`` its control (``reference``);
- ``decode_step(dm, lengths)``, ``prefill_chunk(dm, prefix, chunk)``
  and ``kernels``, each attention kernel's trace name mapped to its
  per-layer count, all as ``work.Work``.

Modules whose name starts with ``_`` hold code that families share.
"""
