"""Drivers of the system under test, one module a kind of system; a
configuration file names its kind under ``"system"``."""
