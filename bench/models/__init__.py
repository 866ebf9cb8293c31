"""One module per model, ``bench/models/<model>.py``, named by the
``model`` key of a configuration file. The harness, the reference and
the check read a model only through these names:

- ``system(model_sizes)``: ``(init_fn, apply_fn)`` of the program's own
  model, as the program's simulator takes them;
- ``ref_init(key, model_sizes, dtype)``: the published initialisation,
  as a pytree in ``dtype``, from the seed's key alone;
- ``ref_apply(params, x, precision)``: the plain forward pass of one
  row's parameters on a batch of inputs ``x`` (images, or int32 token
  ids ``(batch, seq)``), giving logits ``(batch, classes)`` or
  ``(batch, seq, vocab)``; every product at ``precision``;
- ``product(f, a, b, precision)``: a bilinear product ``f(a, b)`` at a
  JAX precision or at ``BF16_3X``;
- ``BF16_3X``: the precision of the ``highest`` control, three bfloat16
  passes in every product;
- ``forward_flops(model_sizes)``: forward operations per sample (per
  sequence, for a token model), a multiply-add counted as 2;
- ``param_count(model_sizes)``: the parameters, from the sizes;
- ``TINY``: the ``model_sizes`` keys a CPU rehearsal shrinks, with their
  tiny values (``bench/tests/tiny.py``); widths may stay published.

The labels a traffic generator makes (``ys``) are int32 class ids with
the shape of the logits less their last axis: ``(batch,)`` for a
classifier, ``(batch, seq)`` for a next-token model.
"""
