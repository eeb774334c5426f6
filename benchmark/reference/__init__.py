"""Plain references: one file per model family, named by a configuration
file's ``reference`` key. They import nothing of the program."""
