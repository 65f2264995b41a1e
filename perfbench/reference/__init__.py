"""Plain references, one module per model family; a configuration file
names its module under ``reference``."""
