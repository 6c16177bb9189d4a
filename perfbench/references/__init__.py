"""Plain float32 references, one module per model kind, named by the
``reference`` key of a configuration file.

A reference module is the one place that knows its kind.  It provides:

* ``layer_shapes(c)``: path -> shape of one layer of each stacked leaf, the
  path starting with its group (``blocks``, or a key ending in ``_blocks``);
* ``block(x, w, c, quant)``: one layer over one sequence, ``x`` of shape
  (T, d) in float32, ``w`` that layer's weights nested without the group
  (``w["attn"]``, ``w["mlp"]``; a module whose layers differ tells them
  apart by the keys of ``w``), ``quant`` the control's rounding or None;
* ``counts(c)``: the FLOP and byte counts, a ``flops.Counts`` or a subclass
  (see ``flops.py`` for what the metric readers call);
* optionally ``layer_weights(seed, c, layer)``: model layer ``layer``'s
  weights, path -> float32 array, as ``weights.layer_weights`` draws them
  from its group at its index there.  Without it model layer ``l`` is layer
  ``l`` of ``blocks`` with the shapes of ``layer_shapes(c)``.

So a configuration of a new kind comes as new files only: its
``configs/<config>.json``, its ``references/<reference>.py``, its cells
(``workloads/``, ``traffic/``) and any metric readers of its own.
"""
