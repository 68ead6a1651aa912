"""Plain references, one file per algorithm; a configuration names its own."""
