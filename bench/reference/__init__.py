"""Plain float32 references, one module per benchmark configuration.

A configuration file names its module (``"reference"``), and the harness
reaches a model's equations only through it. A module supplies:

- ``shape(c)``: the sizes and equations read from the configuration
  file. The shape answers ``kind(layer)`` and ``layer_work(layer)``, the
  products of each layer that ``bench/work.py`` counts;
- ``seed_key(seed)``, ``layer_weights(shape, key, small_key, layer)`` and
  ``head_weights(shape, key, small_key)``: the weights made from the
  seed, named as the program names its leaves (``bench/harness.py``
  hands them to the program);
- ``sequence_logits(shape, seed, sequences, positions, ...)``: the
  reference's logits, which decide ``correct`` (``bench/check.py``);
- ``CONTROL``: for each served width, the precision below it, which the
  comparison has to reject;
- optionally ``EXPERT_WIDTH``: the configuration file's key for the width
  of one expert (``moe_intermediate_size`` where it names none).

``decoder.py`` implements these for decoders whose layers are global or
sliding-window attention and a dense MLP or sparse experts; a module
re-exports what it takes from there and adds what its model needs.
"""
