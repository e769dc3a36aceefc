"""One module per kind of system under test, named by a configuration's
``"system"`` key: it builds the system from the configuration and seed,
warms the shapes the cell's traffic uses, drives the measured window and
checks what the window produced against the configuration's reference."""
