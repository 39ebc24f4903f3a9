"""One module per kind of cell; a traffic file's ``driver`` names it."""
