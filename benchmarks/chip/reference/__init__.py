"""The plain references the output check compares against, one module per
architecture. A configuration file names its own by its ``reference`` key
(``"dense"`` for ``reference/dense.py``); the engine driver loads that
module by name, checks the program's widths by its ``WIDTHS`` and calls its
``gaps``, so an architecture is added as a file here and a configuration
naming it, with no edit to the driver or to ``program.py``.

The contract of a reference module:

- ``WIDTHS``: published ``config`` key -> the program's ``ArchConfig``
  field that must equal it, for every width and size of the architecture
  that the program could get wrong (a cut key too, at its cut value). The
  field names are strings: nothing of the program is imported.
- ``published(c) -> dict``: the configuration's ``config`` with the keys
  the architecture derives filled in (dense: ``head_dim``); ``c`` itself
  where it derives none. Every ``WIDTHS`` key must then be there.
- ``gaps(config, seed, items, *, control=False) -> dict``: ``config`` is
  the configuration file as loaded, ``items`` the sampled requests
  (``{"prompt": [...], "served": [...]}``, greedy tokens). It returns
  ``gap_max``, the widest gap by which a served token's logit lies below
  the reference's best at its position, and with ``control`` also
  ``control_gap_max``, the same read for the token its control puts
  first. Further keys are printed beside them.
- It rebuilds the served weights from the seed, as the configuration
  file's ``weights`` says, one piece at a time.
- It computes in float32 with every product at ``Precision.HIGHEST``.
- It imports nothing of the program and takes nothing the program made.
- Its control computes the same forward in fp8 (float8_e4m3fn), the step
  below the served bfloat16, and a sound check finds it not correct.
"""
