"""One module per architecture, found by the name a configuration file
gives under ``"architecture"`` (``bench/manifest.py``)."""
