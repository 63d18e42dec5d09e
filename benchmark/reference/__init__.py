"""Plain references of the document types the benchmark serves, and the
comparison of a served summary with them.  Nothing here imports the
program."""
