"""The served paths a configuration can name (``"path"``, default
``compress``): one module each, found by ``bench.harness.path_module``."""
