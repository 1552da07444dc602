"""Plain references, one module per architecture family, found by the
name a configuration file gives under ``reference``."""
