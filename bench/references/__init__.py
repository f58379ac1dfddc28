"""Plain float32 forward passes, one module per architecture family.

Each module reads the published configuration (the ``model`` object of a
``configs/<name>.json``), imports nothing of the program under test, and
exposes ``spec``, ``embed``, ``layer`` and ``head``. Layers take a
quantizer ``q`` for the inputs of every matrix product: identity for the
reference, a lower precision for the control.
"""
