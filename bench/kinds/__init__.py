"""Kinds of cell, by ``kind`` in a traffic mix: ``train``.

Each module has ``run(cell, args, env)`` for one run and
``control(cell, seed, log, cache)`` for the readings that set its
limits; a new kind is a new module."""
