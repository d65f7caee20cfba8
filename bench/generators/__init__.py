"""General traffic generators, one module each, named by a traffic file."""
