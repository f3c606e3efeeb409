"""The chip benchmark of the truss decomposition (see ``harness``)."""
