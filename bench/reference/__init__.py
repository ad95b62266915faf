"""Plain float32 references, one module per architecture, named by a
configuration file's ``reference`` key."""
