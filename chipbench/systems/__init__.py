"""Systems under test: one module per configuration ``system`` key."""
