"""Plain references, one per model family; they import nothing of the program."""
