"""Architecture modules: one file per kind of model a configuration can
name with its ``"arch"`` key (see :mod:`bench.arch.dense` for what each
provides)."""
