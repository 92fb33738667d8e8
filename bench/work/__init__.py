"""Work counted from shapes, one file per model family."""
