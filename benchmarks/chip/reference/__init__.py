"""Plain references of the configurations' models, one module per family
of architecture; a configuration file names its module under
``reference``."""
