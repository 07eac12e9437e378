"""The plain reference of every configuration: an exact scan (``scan``)."""
