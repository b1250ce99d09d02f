"""Plain float32 ``jax.numpy`` references, one module per family.

Each module gives ``FIELDS`` (the program's model-config field for
each key of the configuration file, checked before a run) and
``logits_at(conf, weights, tokens, rows, mode)``: the logits of one
sequence's positions ``rows``, from a full causal forward pass with no
cache, batching or kernel.  The references import nothing of the
program: they read the weight tree by its key names, as a loader reads
a checkpoint.
"""
