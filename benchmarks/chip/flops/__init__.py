"""Required operations and bytes per model family, counted from shapes."""
