"""One module per driver kind; a traffic mix names its driver."""
