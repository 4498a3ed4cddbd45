"""One module per traffic driver; a traffic file names its driver."""
