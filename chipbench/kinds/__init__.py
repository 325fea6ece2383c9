"""One driver per kind of configuration, found by the config's `kind`."""
