"""Traffic drivers: one module per traffic ``loop`` key."""
