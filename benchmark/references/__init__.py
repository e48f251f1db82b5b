"""Plain references, one module per model family: a configuration's
``reference`` key (default: its ``model``) names the module."""
