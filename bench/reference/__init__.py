"""Plain references, one module per architecture named in a configuration
file's ``architecture`` key.  They import nothing of the program."""
