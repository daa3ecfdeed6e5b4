"""One file per model family, found by the ``family`` a configuration names:
``<family>.py`` (the protocol: ``constructor``, ``reference``, ``counts``)
with the architecture's plain reference and model-FLOP counts beside it."""
