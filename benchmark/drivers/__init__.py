"""Drivers: one module per kind of configuration, named by its "driver" key."""
