"""Request kinds: how one traffic file turns into requests and checks."""
