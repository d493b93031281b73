"""The only modules of the benchmark that import the program: they build
the system under test the way a user does (``StandardUpdater``,
``GenerationEngine`` + ``GenerationQueue``) from a configuration file."""
