"""Drivers: the only files of the benchmark that import the program. One
per kind of system under test, named by a traffic file's ``driver`` key;
each has ``setup``, ``measure``, ``release`` and ``check``."""
