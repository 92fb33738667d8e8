"""Plain references, one file per model family."""
