"""Architecture families: one file each, found by name.

A configuration file may carry ``"family": "<name>"`` (``dense`` when it
does not); ``chipbench.spec.family`` loads ``families/<name>.py``. A
family file defines:

- ``Sizes``: a frozen dataclass of the model as the configuration's
  ``model`` section states it, with at least ``n_layers``, ``vocab`` and
  ``dtype`` (the dtype the weights are served in);
- ``sizes_of(cfg)``: the program's ``LMConfig`` as those sizes;
- ``program_config(cfg, reduced)``: the program's config with the
  configuration's ``reduced`` keys applied; a key that lies in a nested
  group of the program's config is mapped here and nowhere else;
- ``recipe(sizes)``: the weight leaves, a ``chipbench.weights.Recipe``;
  each leaf states how many layers it is stacked over, or ``None``;
- ``Reference(sizes, seed)`` with ``logits(tokens, first, fp8=False)``:
  plain float32 at ``Precision.HIGHEST``, each layer drawn again through
  ``chipbench.weights.Redraw``, importing nothing of ``repro``;
- ``chunk(sizes, rows, prompt_len, decode_tokens)``: the
  ``(prefill, decode)`` ``chipbench.work.Work`` of one served chunk.

The harness reaches a family only through these names, and the common
code (weights, work, the check) through the family of the ``Sizes`` it is
handed, so a new family is a new file.
"""
