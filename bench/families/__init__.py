"""One module per model family, found by the ``family`` key of a
configuration file (``bench/families/<family>.py``) and loaded by path.

A family module gives every seam of the harness that depends on the
model's architecture:

- ``program_config(cj)``: the program's ``ModelConfig`` for a
  configuration file's keys;
- ``weight_shapes(cj)``: the parameter tree's shapes, which
  ``bench.weights.make`` draws;
- ``work_model(cj)`` and ``prefill_step``, ``prefill_attention``,
  ``decode_step``, ``decode_attention``: what one step's work needs, each
  called with the work model and the step's prompt lengths or live
  contexts and returning a work dict as ``bench.counts`` does
  (``flops``, ``bytes``, and ``calls`` for attention);
- ``reference``: the plain reference, with ``gaps(params, cj, tokens,
  query, quant=None)``;
- ``warm_plans(cfg, serve)``: plan requests to warm beyond the engine's
  own warmup, for the cell's serving settings;
- ``smoke(cj)``: the configuration at test size.
"""
